"""jit wrapper: model layout -> kernel layout, padding, backend selection."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention import kernel as _k
from repro.kernels.decode_attention import ref as _ref


@functools.partial(jax.jit, static_argnames=("block_kv", "force_ref",
                                             "scale"))
def decode_attention(q, k, v, valid, *, block_kv: int = _k.DEFAULT_BLOCK_KV,
                     force_ref: bool = False, scale: float | None = None):
    """Model layout: q (B, 1, H, D); k/v (B, T, KV, D); valid (T,) bool/int.
    Returns (B, 1, H, D). ``scale`` multiplies the scores (default
    D^-0.5)."""
    if force_ref:
        return _ref.decode_attention_ref(q, k, v, valid, scale=scale)
    b, _, h, d = q.shape
    t, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    bk = min(block_kv, max(8, 1 << (t - 1).bit_length()))
    pad = (-t) % bk
    kt = k.transpose(0, 2, 1, 3)                      # (B, KV, T, D)
    vt = v.transpose(0, 2, 1, 3)
    vmask = (valid > 0).astype(jnp.int32)[None, :]    # (1, T)
    if pad:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vmask = jnp.pad(vmask, ((0, 0), (0, pad)))    # padded slots invalid
    qg = q.reshape(b, kvh, g, d)
    interpret = jax.default_backend() != "tpu"
    o = _k.decode_attention_grouped(qg, kt, vt, vmask, block_kv=bk,
                                    scale=scale, interpret=interpret)
    return o.reshape(b, 1, h, d)
