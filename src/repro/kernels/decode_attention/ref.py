"""Pure-jnp oracle for single-token decode attention with slot validity."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def decode_attention_ref(q, k, v, valid, layer=None, scale=None):
    """q (B, 1, H, D); k/v (B, KV, D, T), slots minor, or layer-stacked
    (L, B, KV, D, T) read at ``layer``; valid (T,) bool/int.
    Returns (B, 1, H, D)."""
    if layer is not None:
        k, v = k[layer], v[layer]
    b, _, h, d = q.shape
    kvh = k.shape[1]
    qf = q.astype(jnp.float32).reshape(b, kvh, h // kvh, d)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    s = jnp.einsum("bkgd,bkdt->bkgt", qf, kf) * (
        d ** -0.5 if scale is None else scale)
    s = jnp.where((valid > 0)[None, None, None, :], s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgt,bkdt->bkgd", w, vf)
    return o.reshape(b, 1, h, d).astype(q.dtype)
