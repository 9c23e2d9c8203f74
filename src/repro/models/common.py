"""Shared layer primitives: inits, norms, activations, RoPE, logical sharding
annotations.

Parameters are plain nested dicts of jnp arrays. Activation sharding hints use
``logical_constraint`` with *logical axis names*; parallel/sharding.py resolves
them against the active mesh (and drops non-divisible axes).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

# Logical activation axes -> resolved by parallel/sharding.py
BATCH = "act_batch"
SEQ = "act_seq"
HEADS = "act_heads"
KV_SEQ = "act_kv_seq"
FF = "act_ff"
EXPERT = "act_expert"
EMBED = "act_embed"
VOCAB = "act_vocab"

_MESH_RULES_STACK: list = []

# Runtime execution knobs, set by the launcher per (backend, shape):
#   use_flash    — route attention through the Pallas kernels (TPU)
#   q_chunk      — flash-style q-block chunking for attention/MLA in pure
#                  XLA (the shardable dry-run path; 0 = full quadratic)
#   ssm_chunk    — chunkwise Mamba scan (bounds associative-scan live set)
#   mlstm_chunk  — chunkwise-recurrent mLSTM (bounds the quadratic form)
RUNTIME = {"use_flash": False, "q_chunk": 0, "ssm_chunk": 0,
           "mlstm_chunk": 0, "moe_chunk": 0, "remat_policy": "",
           "moe_combine_bf16": False, "moe_capacity_factor": 0.0}


def push_logical_rules(rules):
    _MESH_RULES_STACK.append(rules)


def pop_logical_rules():
    _MESH_RULES_STACK.pop()


def logical_constraint(x: jnp.ndarray, *axes: Optional[str]) -> jnp.ndarray:
    """Annotate activation sharding if a rule set is active (no-op otherwise)."""
    if not _MESH_RULES_STACK:
        return x
    resolver = _MESH_RULES_STACK[-1]
    spec = resolver(x.shape, axes)
    if spec is None:
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def truncnorm_init(key, shape, scale=0.02, dtype=jnp.float32):
    return (scale * jax.random.truncated_normal(key, -2.0, 2.0, shape)).astype(dtype)


def dense_init(key, d_in, d_out, dtype=jnp.float32, scale=None):
    scale = scale if scale is not None else d_in ** -0.5
    return truncnorm_init(key, (d_in, d_out), scale, dtype)


def rmsnorm_params(d):
    return {"scale": jnp.ones((d,), jnp.float32)}


def layernorm_params(d):
    return {"scale": jnp.ones((d,), jnp.float32), "bias": jnp.zeros((d,), jnp.float32)}


def apply_norm(p, x, kind: str, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    if kind == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
        out = (xf - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    else:  # rmsnorm
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        out = xf * jax.lax.rsqrt(ms + eps) * p["scale"]
    return out.astype(x.dtype)


def activate(x, kind: str):
    if kind == "gelu":
        return jax.nn.gelu(x)
    return jax.nn.silu(x)


# ---------------------------------------------------------------------------
# Rotary position embeddings (GPT-NeoX half-rotation convention).
# ---------------------------------------------------------------------------
def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
               freqs=None) -> jnp.ndarray:
    """x: (..., seq, heads, head_dim); positions: (..., seq) int32.
    ``freqs`` (head_dim // 2,) replaces theta's inverse frequencies."""
    half = x.shape[-1] // 2
    if freqs is None:
        freqs = rope_freqs(x.shape[-1], theta)                # (half,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., seq, half)
    cos = jnp.cos(angles)[..., None, :]                        # (..., seq, 1, half)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([x1f * cos - x2f * sin, x2f * cos + x1f * sin], axis=-1)
    return out.astype(x.dtype)


def causal_mask(q_pos: jnp.ndarray, k_pos: jnp.ndarray,
                window: Optional[int] = None) -> jnp.ndarray:
    """(..., q, k) boolean mask: True = attend. Sliding window if set."""
    m = k_pos[..., None, :] <= q_pos[..., :, None]
    if window is not None:
        m &= k_pos[..., None, :] > (q_pos[..., :, None] - window)
    return m


def cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray,
                  mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Mean NLL over (optionally masked) positions; logits fp32."""
    logits = logits.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
