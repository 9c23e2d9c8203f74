"""Pure-jnp oracle for flash attention (GQA + causal + sliding window)."""
from __future__ import annotations

import jax.numpy as jnp
import jax


def attention_ref(q, k, v, *, causal: bool = True, window=None, scale=None):
    """q (B, S, H, D); k/v (B, T, KV, D) -> (B, S, H, D), fp32 math."""
    b, s, h, d = q.shape
    t, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    qf = q.astype(jnp.float32).reshape(b, s, kvh, g, d)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    scores = jnp.einsum("bskgd,btkd->bksgt", qf, kf) * (
        d ** -0.5 if scale is None else scale)
    qpos = jnp.arange(s)[:, None]
    kpos = jnp.arange(t)[None, :]
    mask = jnp.ones((s, t), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    scores = jnp.where(mask[None, None, :, None, :], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bksgt,btkd->bskgd", w, vf)
    return out.reshape(b, s, h, d).astype(q.dtype)
