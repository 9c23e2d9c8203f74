"""Pure-jnp oracle for single-token decode attention with slot validity."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def decode_attention_ref(q, k, v, valid, scale=None):
    """q (B, 1, H, D); k/v (B, T, KV, D); valid (T,) bool/int.
    Returns (B, 1, H, D)."""
    b, _, h, d = q.shape
    t, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    qf = q.astype(jnp.float32).reshape(b, kvh, g, d)
    kf = k.astype(jnp.float32).transpose(0, 2, 1, 3)       # (B, KV, T, D)
    vf = v.astype(jnp.float32).transpose(0, 2, 1, 3)
    s = jnp.einsum("bkgd,bktd->bkgt", qf, kf) * (
        d ** -0.5 if scale is None else scale)
    s = jnp.where((valid > 0)[None, None, None, :], s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgt,bktd->bkgd", w, vf)
    return o.reshape(b, 1, h, d).astype(q.dtype)
