"""jit wrapper: model layout -> kernel layout, backend selection."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention import kernel as _k


@functools.partial(jax.jit, static_argnames=("scale",))
def decode_attention(q, k, v, valid, layer=None, *,
                     scale: float | None = None):
    """Model layout: q (B, 1, H, D); k/v (B, KV, D, T), slots minor, or
    layer-stacked (L, B, KV, D, T) and read in place at ``layer`` (int32
    scalar); valid (T,) bool/int. Returns (B, 1, H, D). ``scale``
    multiplies the scores (default D^-0.5)."""
    if layer is None:
        k, v, layer = k[None], v[None], 0
    b, _, h, d = q.shape
    kvh = k.shape[2]
    qg = q.reshape(b, kvh, h // kvh, d)
    vmask = (valid > 0).astype(jnp.int32)[None, :]    # (1, T)
    interpret = jax.default_backend() != "tpu"
    o = _k.decode_attention_grouped(
        qg, k, v, vmask, jnp.reshape(layer, (1,)).astype(jnp.int32),
        scale=scale, interpret=interpret)
    return o.reshape(b, 1, h, d)
