"""jit wrapper: layout conversion, padding to block multiples, backend
selection (Pallas on TPU / interpret elsewhere / jnp reference fallback)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import kernel as _k
from repro.kernels.flash_attention import ref as _ref


def _pad_to(x, axis, mult):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_kv", "force_ref", "scale"))
def flash_attention(q, k, v, *, causal: bool = True, window=None,
                    block_q: int = _k.DEFAULT_BLOCK_Q,
                    block_kv: int = _k.DEFAULT_BLOCK_KV,
                    force_ref: bool = False, scale: float | None = None):
    """Public API — model layout: q (B, S, H, D); k/v (B, T, KV, D).
    ``scale`` multiplies the scores (default D^-0.5)."""
    if force_ref:
        return _ref.attention_ref(q, k, v, causal=causal, window=window,
                                  scale=scale)
    b, s, h, d = q.shape
    t = k.shape[1]
    bq = min(block_q, max(8, 1 << (s - 1).bit_length()))
    bk = min(block_kv, max(8, 1 << (t - 1).bit_length()))
    qt = _pad_to(q.transpose(0, 2, 1, 3), 2, bq)       # (B, H, S', D)
    kt = _pad_to(k.transpose(0, 2, 1, 3), 2, bk)       # (B, KV, T', D)
    vt = _pad_to(v.transpose(0, 2, 1, 3), 2, bk)
    interpret = jax.default_backend() != "tpu"
    o = _k.flash_attention_bhsd(qt, kt, vt, causal=causal, window=window,
                                block_q=bq, block_kv=bk, seq_kv=t,
                                scale=scale, interpret=interpret)
    return o[:, :, :s].transpose(0, 2, 1, 3)
