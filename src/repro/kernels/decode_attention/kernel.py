"""Pallas TPU flash-decode: one query token vs a blocked KV cache.

The decode_32k / long_500k hot spot is memory-bound (the whole KV cache
streams HBM->VMEM once per token). The kernel tiles the cache T dim; the
running (m, l, acc) online-softmax state lives in VMEM scratch across the kv
grid dim. Validity is a per-slot int32 mask (ring caches mark stale slots),
so the same kernel serves full and sliding-window caches.

Layout: q (B, KV, G, D); k/v layer-stacked (L, B, KV, D, T), slots minor,
read at the scalar-prefetched layer index where they lie (no slab is sliced
out, relaid or padded first); valid (1, T) int32 -> o (B, KV, G, D).
Blocks: ``bt`` slots, a multiple of 128 that divides T (or T itself when it
is not a multiple of 128), and ``hb`` kv heads per grid step, both the
largest whose K and V blocks fit ``BLOCK_BYTES``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_BYTES = 2 << 20     # K and V bytes of one grid step (double-buffered)
NEG_INF = -1e30


def blocks(kvh: int, d: int, t: int, itemsize: int) -> tuple[int, int]:
    """(kv heads, slots) of one grid step for a (KV, D, T) cache."""
    def pair(bt):                 # one head's K and V block bytes
        return 2 * d * bt * itemsize
    if t % 128:
        bt = t
    else:
        bt = max((m for m in range(128, t + 1, 128)
                  if t % m == 0 and pair(m) <= BLOCK_BYTES), default=128)
    hb = max(h for h in range(1, kvh + 1)
             if kvh % h == 0 and (h == 1 or h * pair(bt) <= BLOCK_BYTES))
    return hb, bt


def _decode_kernel(layer_ref, q_ref, k_ref, v_ref, valid_ref, o_ref, m_scr,
                   l_scr, acc_scr, *, scale: float):
    del layer_ref                                  # used by the index maps
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    valid = valid_ref[...] > 0                     # (1, bt)
    for h in range(q_ref.shape[1]):                # kv heads of this step
        q = q_ref[0, h].astype(jnp.float32)        # (G, D) — group heads
        k = k_ref[0, 0, h].astype(jnp.float32)     # (D, bt)
        v = v_ref[0, 0, h].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(valid, s, NEG_INF)           # (G, bt)

        m_prev = m_scr[h][:, 0]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, None])
        l_scr[h] = (l_scr[h][:, 0] * alpha + jnp.sum(p, axis=1))[:, None]
        acc_scr[h] = acc_scr[h] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[h] = m_cur[:, None]

    @pl.when(ki == nk - 1)
    def _done():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def decode_attention_grouped(q, k, v, valid, layer, *,
                             block_kv: int | None = None,
                             scale: float | None = None,
                             interpret: bool = True):
    """q (B, KV, G, D) — queries grouped by kv head; k/v (L, B, KV, D, T);
    valid (1, T) int32; layer (1,) int32, the slab of k/v to read. Returns
    (B, KV, G, D). ``scale`` multiplies the scores (default D^-0.5);
    ``block_kv`` overrides the slots per block (it must divide T)."""
    b, kvh, g, d = q.shape
    t = k.shape[-1]
    hb, bt = blocks(kvh, d, t, k.dtype.itemsize)
    bt = block_kv or bt
    kernel = functools.partial(_decode_kernel,
                               scale=d ** -0.5 if scale is None else scale)
    kv_spec = pl.BlockSpec((1, 1, hb, d, bt),
                           lambda bb, hh, ki, lr: (lr[0], bb, hh, 0, ki))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, kvh // hb, t // bt),
        in_specs=[
            pl.BlockSpec((1, hb, g, d), lambda bb, hh, ki, lr: (bb, hh, 0, 0)),
            kv_spec,
            kv_spec,
            pl.BlockSpec((1, bt), lambda bb, hh, ki, lr: (0, ki)),
        ],
        out_specs=pl.BlockSpec((1, hb, g, d),
                               lambda bb, hh, ki, lr: (bb, hh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((hb, g, 1), jnp.float32),
            pltpu.VMEM((hb, g, 1), jnp.float32),
            pltpu.VMEM((hb, g, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, d), q.dtype),
        interpret=interpret,
    )(layer, q, k, v, valid)
