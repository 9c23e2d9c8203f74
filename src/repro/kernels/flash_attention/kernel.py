"""Pallas TPU flash attention (training / prefill hot spot).

TPU-native adaptation (DESIGN.md SS3): q-block x kv-block tiles sized for
VMEM, MXU-aligned (128-multiples), online softmax with running (m, l, acc)
carried in VMEM scratch across the kv grid dimension (TPU grids execute the
innermost dimension sequentially per core — the accumulator pattern MaxText
uses). Supports causal + sliding-window masks and GQA via the kv-head
index map (no KV repetition in HBM).

Layout: q (B, H, S, D), k/v (B, KV, T, D) -> o (B, H, S, D).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_KV = 128
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, window, seq_kv: int,
                  block_q: int, block_kv: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)            # (BQ, D)
    k = k_ref[0, 0].astype(jnp.float32)            # (BK, D)
    v = v_ref[0, 0].astype(jnp.float32)            # (BK, D)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_kv), 0)
    kpos = ki * block_kv + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_kv), 1)
    mask = kpos < seq_kv                            # kv padding guard
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...][:, 0]                       # (BQ,)
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_cur)                 # (BQ,)
    p = jnp.exp(s - m_cur[:, None])                 # (BQ, BK)
    l_scr[...] = (l_scr[...][:, 0] * alpha + jnp.sum(p, axis=1))[:, None]
    acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_cur[:, None]

    @pl.when(ki == nk - 1)
    def _done():
        l = jnp.maximum(l_scr[...], 1e-30)          # (BQ, 1)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def flash_attention_bhsd(q, k, v, *, causal: bool = True, window=None,
                         block_q: int = DEFAULT_BLOCK_Q,
                         block_kv: int = DEFAULT_BLOCK_KV,
                         seq_kv: int | None = None,
                         scale: float | None = None,
                         interpret: bool = True):
    """q (B, H, Sq, D); k/v (B, KV, Skv, D); H % KV == 0. Sq/Skv must be
    multiples of the block sizes (ops.py pads; seq_kv = true unpadded kv
    length for the padding mask). ``scale`` multiplies the scores
    (default D^-0.5)."""
    b, h, sq, d = q.shape
    _, kv, skv, _ = k.shape
    assert h % kv == 0, (h, kv)
    group = h // kv
    nq, nk = sq // block_q, skv // block_kv
    grid = (b, h, nq, nk)
    kernel = functools.partial(
        _flash_kernel, scale=d ** -0.5 if scale is None else scale,
        causal=causal, window=window,
        seq_kv=seq_kv if seq_kv is not None else skv,
        block_q=block_q, block_kv=block_kv)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bb, hh, qi, ki: (bb, hh, qi, 0)),
            pl.BlockSpec((1, 1, block_kv, d),
                         lambda bb, hh, qi, ki, _g=group: (bb, hh // _g, ki, 0)),
            pl.BlockSpec((1, 1, block_kv, d),
                         lambda bb, hh, qi, ki, _g=group: (bb, hh // _g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda bb, hh, qi, ki: (bb, hh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
