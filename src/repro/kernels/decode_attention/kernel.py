"""Pallas TPU flash-decode: one query token vs a blocked KV cache.

The decode_32k / long_500k hot spot is memory-bound (the whole KV cache
streams HBM->VMEM once per token). The kernel tiles the cache T dim; the
running (m, l, acc) online-softmax state lives in VMEM scratch across the kv
grid dim. Validity is a per-slot int32 mask (ring caches mark stale slots),
so the same kernel serves full and sliding-window caches.

Layout: q (B, H, D); k/v (B, KV, T, D); valid (T,) int32 -> o (B, H, D).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_KV = 512
NEG_INF = -1e30


def _decode_kernel(q_ref, k_ref, v_ref, valid_ref, o_ref, m_scr, l_scr,
                   acc_scr, *, scale: float):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)            # (G, D) — group heads
    k = k_ref[0, 0].astype(jnp.float32)            # (BK, D)
    v = v_ref[0, 0].astype(jnp.float32)
    valid = valid_ref[...] > 0                     # (1, BK)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(valid, s, NEG_INF)               # (H, BK)

    m_prev = m_scr[...][:, 0]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur[:, None])
    l_scr[...] = (l_scr[...][:, 0] * alpha + jnp.sum(p, axis=1))[:, None]
    acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_cur[:, None]

    @pl.when(ki == nk - 1)
    def _done():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def decode_attention_grouped(q, k, v, valid, *,
                             block_kv: int = DEFAULT_BLOCK_KV,
                             scale: float | None = None,
                             interpret: bool = True):
    """q (B, KV, G, D) — queries grouped by kv head; k/v (B, KV, T, D);
    valid (1, T) int32. Returns (B, KV, G, D). ``scale`` multiplies the
    scores (default D^-0.5)."""
    b, kvh, g, d = q.shape
    t = k.shape[2]
    nk = t // block_kv
    grid = (b, kvh, nk)
    kernel = functools.partial(_decode_kernel,
                               scale=d ** -0.5 if scale is None else scale)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, g, d), lambda bb, hh, ki: (bb, hh, 0, 0)),
            pl.BlockSpec((1, 1, block_kv, d),
                         lambda bb, hh, ki: (bb, hh, ki, 0)),
            pl.BlockSpec((1, 1, block_kv, d),
                         lambda bb, hh, ki: (bb, hh, ki, 0)),
            pl.BlockSpec((1, block_kv), lambda bb, hh, ki: (0, ki)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d), lambda bb, hh, ki: (bb, hh, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, valid)
