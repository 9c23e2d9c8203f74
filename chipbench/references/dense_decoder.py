"""Plain reference of a dense decoder-only transformer, and its weights.

Pre-norm blocks: x += Attn(Norm(x)); x += MLP(Norm(x)); logits =
Norm(x) @ head. Attention is causal (and windowed where the configuration
sets ``sliding_window``), with grouped key/value heads and rotary embeddings
on the two halves of each head (GPT-NeoX order). The MLP is gated SiLU
(``silu``) or plain tanh-approximated GeLU (``gelu_pytorch_tanh``). Norms are
RMSNorm or LayerNorm with unit scale and zero bias.

Written from the configuration file alone; it imports nothing of the
program. Weights come from the seed, one layer at a time, so the reference
runs layer by layer in float32 at ``highest`` matmul precision and holds one
layer's weights at a time. ``quantize`` puts every matmul's operands through
float8 (e4m3, one scale per tensor): the control that a lower precision must
fail.
"""
from __future__ import annotations

import dataclasses
import functools
import zlib

import jax
import jax.numpy as jnp
import numpy as np

EMBED_STD = 0.02   # embedding and output head, as the program initialises them


@dataclasses.dataclass(frozen=True)
class Spec:
    d: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    norm: str           # rmsnorm | layernorm
    act: str            # silu | gelu_pytorch_tanh
    tied: bool
    rope_theta: float
    eps: float
    window: int | None

    @classmethod
    def from_config(cls, c: dict) -> "Spec":
        return cls(d=c["hidden_size"], layers=c["num_hidden_layers"],
                   heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"],
                   head_dim=c["hidden_size"] // c["num_attention_heads"],
                   d_ff=c["intermediate_size"], vocab=c["vocab_size"],
                   norm=c["norm"], act=c["hidden_act"],
                   tied=c["tie_word_embeddings"],
                   rope_theta=float(c["rope_theta"]),
                   eps=float(c.get("rms_norm_eps", c.get("norm_epsilon"))),
                   window=c.get("sliding_window"))


# ---------------------------------------------------------------------------
# Weights: each (leaf, layer) from its own key, uniform with the stated std
# ---------------------------------------------------------------------------
def layer_shapes(s: Spec) -> dict:
    """Matmul weights of one layer: name -> (shape, std)."""
    hd, kd = s.heads * s.head_dim, s.kv_heads * s.head_dim
    shapes = {"wq": ((s.d, hd), s.d ** -0.5), "wk": ((s.d, kd), s.d ** -0.5),
              "wv": ((s.d, kd), s.d ** -0.5), "wo": ((hd, s.d), hd ** -0.5),
              "w_up": ((s.d, s.d_ff), s.d ** -0.5),
              "w_down": ((s.d_ff, s.d), s.d_ff ** -0.5)}
    if s.act == "silu":
        shapes["w_gate"] = ((s.d, s.d_ff), s.d ** -0.5)
    return shapes


def leaf(key, name: str, layer, shape, std: float):
    """bfloat16 values of one leaf of one layer (``layer`` may be traced)."""
    k = jax.random.fold_in(jax.random.fold_in(key, zlib.crc32(name.encode())),
                           layer)
    a = std * np.sqrt(3.0)
    return jax.random.uniform(k, shape, jnp.float32, -a, a).astype(jnp.bfloat16)


def embed_weights(s: Spec, key) -> dict:
    out = {"embed": leaf(key, "embed", 0, (s.vocab, s.d), EMBED_STD)}
    if not s.tied:
        out["lm_head"] = leaf(key, "lm_head", 0, (s.d, s.vocab), EMBED_STD)
    return out


def layer_weights(s: Spec, key, layer) -> dict:
    return {n: leaf(key, n, layer, shape, std)
            for n, (shape, std) in layer_shapes(s).items()}


# ---------------------------------------------------------------------------
# Forward, layer by layer
# ---------------------------------------------------------------------------
def _q8(x):
    """float8 e4m3 round trip with one scale per tensor."""
    x = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(eq: str, a, b, quantize: bool):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if quantize:
        a, b = _q8(a), _q8(b)
    return jnp.einsum(eq, a, b, precision=jax.lax.Precision.HIGHEST)


def _norm(s: Spec, x):
    if s.norm == "layernorm":
        mu = x.mean(-1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(((x - mu) ** 2).mean(-1, keepdims=True)
                                        + s.eps)
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + s.eps)


def _rope(s: Spec, x, pos):
    """x (N, T, heads, D); rotate (x1, x2) halves by pos * theta^(-2i/D)."""
    half = s.head_dim // 2
    freq = 1.0 / (s.rope_theta ** (np.arange(half, dtype=np.float32) / half))
    ang = pos[:, None].astype(jnp.float32) * freq[None, :]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _act(s: Spec, w: dict, h, quantize: bool):
    up = _mm("ntd,df->ntf", h, w["w_up"], quantize)
    if s.act == "silu":
        return jax.nn.silu(_mm("ntd,df->ntf", h, w["w_gate"], quantize)) * up
    return jax.nn.gelu(up, approximate=True)


@functools.partial(jax.jit, static_argnums=(0, 4))
def layer_forward(s: Spec, key, layer, x, quantize: bool):
    """One block over x (N, T, d) float32."""
    w = layer_weights(s, key, layer)
    n, t, _ = x.shape
    pos = jnp.arange(t)
    h = _norm(s, x)
    q = _mm("ntd,de->nte", h, w["wq"], quantize).reshape(
        n, t, s.heads, s.head_dim)
    k = _mm("ntd,de->nte", h, w["wk"], quantize).reshape(
        n, t, s.kv_heads, s.head_dim)
    v = _mm("ntd,de->nte", h, w["wv"], quantize).reshape(
        n, t, s.kv_heads, s.head_dim)
    q, k = _rope(s, q, pos), _rope(s, k, pos)
    g = s.heads // s.kv_heads
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    mask = pos[None, :] <= pos[:, None]
    if s.window is not None:
        mask &= pos[None, :] > pos[:, None] - s.window
    scores = _mm("nqhd,nkhd->nhqk", q, k, quantize) / np.sqrt(s.head_dim)
    p = jax.nn.softmax(jnp.where(mask[None, None], scores, -jnp.inf), -1)
    o = _mm("nhqk,nkhd->nqhd", p, v, quantize).reshape(n, t, -1)
    x = x + _mm("nte,ed->ntd", o, w["wo"], quantize)
    h = _act(s, w, _norm(s, x), quantize)
    return x + _mm("ntf,fd->ntd", h, w["w_down"], quantize)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _embed(s: Spec, key, tokens, quantize: bool):
    e = embed_weights(s, key)["embed"][tokens].astype(jnp.float32)
    return _q8(e) if quantize else e


@functools.partial(jax.jit, static_argnums=(0, 3))
def _head(s: Spec, key, x, quantize: bool):
    w = embed_weights(s, key)
    head = w["embed"].T if s.tied else w["lm_head"]
    return _mm("ntd,dv->ntv", _norm(s, x), head, quantize)


def logits(s: Spec, key, tokens: np.ndarray, first: int,
           quantize: bool = False) -> np.ndarray:
    """float32 logits (N, T - first, V) at positions first..T-1 of
    ``tokens`` (N, T)."""
    x = _embed(s, key, jnp.asarray(tokens), quantize)
    for layer in range(s.layers):
        x = layer_forward(s, key, jnp.int32(layer), x, quantize)
    return np.asarray(_head(s, key, x[:, first:], quantize))


def widest_gap(ref: np.ndarray, chosen: np.ndarray) -> float:
    """Largest amount by which a chosen token's reference logit lies below
    the reference's best at its position. ref (N, T, V); chosen (N, T)."""
    got = np.take_along_axis(ref, chosen[..., None], -1)[..., 0]
    return float(np.max(ref.max(-1) - got))
