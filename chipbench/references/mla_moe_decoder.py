"""Plain reference of a DeepSeek-V2 decoder share, and its weights.

Pre-norm blocks: x += MLA(RMSNorm(x)); x += FFN(RMSNorm(x)); logits =
RMSNorm(x) @ head. The first ``first_k_dense_replace`` layers have a dense
gated-SiLU FFN, the rest a mixture of experts.

MLA as published, un-absorbed: q = RMSNorm(h W_qa) W_qb per head split into
q_nope (128) and q_rope (64); the token's latent row is RMSNorm(c_kv) and a
shared k_rope from h W_kva; K and V are expanded per head from the latent by
W_kvb, k = k_nope ‖ k_rope. The rope dims use YaRN (DeepSeek-V2's
``rope_scaling``): inverse frequencies blended between theta's and theta's
over ``factor`` along a linear ramp between the correction dims of
``beta_fast`` and ``beta_slow``, cos/sin times mscale(mscale) /
mscale(mscale_all_dim), and the softmax scale (qk_nope + qk_rope)^-0.5 times
mscale(mscale_all_dim)^2, with mscale(m) = 0.1 m ln(factor) + 1. Rope pairs
are the two halves of the rope dims (GPT-NeoX order); DeepSeek-V2's own code
de-interleaves adjacent pairs first, which with random weights is a fixed
permutation of the rope columns of W_qb and W_kva.

The MoE layer is this chip's share of an expert-parallel deployment: the
router scores all ``router_experts`` (softmax), routes by group-limited
greedy (each of ``n_group`` groups scored by its best expert, the token keeps
``topk_group`` groups and takes its top ``num_experts_per_tok`` experts
among them), weights are the experts' probabilities times
``routed_scaling_factor`` (renormalised first only if ``norm_topk_prob``).
Of the result, the part of the ``n_routed_experts`` experts held here
(``first_local_expert`` on) is computed, every held expert over every token
with its weight (zero where not routed), and the shared experts are added.

Written from the configuration file alone; it imports nothing of the
program. Weights come from the seed, one layer at a time, as in
``dense_decoder``; the reference runs layer by layer in float32 at
``highest`` matmul precision. ``quantize`` puts every matmul's operands
through float8 (e4m3, one scale per tensor): the control. ``forward`` also
counts, per position, the (token, expert) pairs its routing sends to the
held experts over all MoE layers.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.references.dense_decoder import (EMBED_STD, _mm, _q8, leaf,
                                                widest_gap)

__all__ = ["Spec", "embed_weights", "layer_weights", "logits", "widest_gap"]


@dataclasses.dataclass(frozen=True)
class Spec:
    d: int
    layers: int
    first_dense: int
    heads: int
    q_lora: int
    kv_lora: int
    nope: int
    rope: int
    v_dim: int
    d_ff: int
    expert_ff: int
    router_experts: int
    held: int
    first_local: int
    n_group: int
    topk_group: int
    top_k: int
    n_shared: int
    routed_scale: float
    norm_topk: bool
    vocab: int
    eps: float
    rope_theta: float
    yarn_factor: float
    yarn_mscale: float
    yarn_mscale_all_dim: float
    yarn_original_max: int
    yarn_beta_fast: float
    yarn_beta_slow: float

    @classmethod
    def from_config(cls, c: dict) -> "Spec":
        y = c["rope_scaling"]
        return cls(d=c["hidden_size"], layers=c["num_hidden_layers"],
                   first_dense=c["first_k_dense_replace"],
                   heads=c["num_attention_heads"], q_lora=c["q_lora_rank"],
                   kv_lora=c["kv_lora_rank"], nope=c["qk_nope_head_dim"],
                   rope=c["qk_rope_head_dim"], v_dim=c["v_head_dim"],
                   d_ff=c["intermediate_size"],
                   expert_ff=c["moe_intermediate_size"],
                   router_experts=c["router_experts"],
                   held=c["n_routed_experts"],
                   first_local=c["first_local_expert"],
                   n_group=c["n_group"], topk_group=c["topk_group"],
                   top_k=c["num_experts_per_tok"],
                   n_shared=c["n_shared_experts"],
                   routed_scale=float(c["routed_scaling_factor"]),
                   norm_topk=c["norm_topk_prob"], vocab=c["vocab_size"],
                   eps=float(c["rms_norm_eps"]),
                   rope_theta=float(c["rope_theta"]),
                   yarn_factor=float(y["factor"]),
                   yarn_mscale=float(y["mscale"]),
                   yarn_mscale_all_dim=float(y["mscale_all_dim"]),
                   yarn_original_max=y["original_max_position_embeddings"],
                   yarn_beta_fast=float(y["beta_fast"]),
                   yarn_beta_slow=float(y["beta_slow"]))

    def is_moe(self, layer: int) -> bool:
        return layer >= self.first_dense


# ---------------------------------------------------------------------------
# Weights: each (leaf, layer) from its own key, uniform with the stated std
# ---------------------------------------------------------------------------
def layer_shapes(s: Spec, moe: bool) -> dict:
    """Matmul weights of one layer: name -> (shape, std). Norm scales are
    1 and not listed."""
    qd = s.nope + s.rope
    ho = s.heads * s.v_dim
    shapes = {"wq_a": ((s.d, s.q_lora), s.d ** -0.5),
              "wq_b": ((s.q_lora, s.heads * qd), s.q_lora ** -0.5),
              "wkv_a": ((s.d, s.kv_lora + s.rope), s.d ** -0.5),
              "wkv_b": ((s.kv_lora, s.heads * (s.nope + s.v_dim)),
                        s.kv_lora ** -0.5),
              "wo": ((ho, s.d), ho ** -0.5)}
    if not moe:
        shapes.update(w_up=((s.d, s.d_ff), s.d ** -0.5),
                      w_gate=((s.d, s.d_ff), s.d ** -0.5),
                      w_down=((s.d_ff, s.d), s.d_ff ** -0.5))
        return shapes
    f, fs = s.expert_ff, s.expert_ff * s.n_shared
    shapes.update(router=((s.d, s.router_experts), s.d ** -0.5),
                  e_up=((s.held, s.d, f), s.d ** -0.5),
                  e_gate=((s.held, s.d, f), s.d ** -0.5),
                  e_down=((s.held, f, s.d), f ** -0.5))
    if s.n_shared:
        shapes.update(s_up=((s.d, fs), s.d ** -0.5),
                      s_gate=((s.d, fs), s.d ** -0.5),
                      s_down=((fs, s.d), fs ** -0.5))
    return shapes


def embed_weights(s: Spec, key) -> dict:
    return {"embed": leaf(key, "embed", 0, (s.vocab, s.d), EMBED_STD),
            "lm_head": leaf(key, "lm_head", 0, (s.d, s.vocab), EMBED_STD)}


def layer_weights(s: Spec, key, layer, moe: bool) -> dict:
    return {n: leaf(key, n, layer, shape, std)
            for n, (shape, std) in layer_shapes(s, moe).items()}


# ---------------------------------------------------------------------------
# YaRN
# ---------------------------------------------------------------------------
def yarn_mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn_ramp_dims(s: Spec) -> tuple[int, int]:
    def corr(rot):
        return (s.rope * math.log(s.yarn_original_max / (rot * 2 * math.pi))
                / (2 * math.log(s.rope_theta)))
    low = max(math.floor(corr(s.yarn_beta_fast)), 0)
    high = min(math.ceil(corr(s.yarn_beta_slow)), s.rope - 1)
    return low, high


def yarn_inv_freq(s: Spec) -> np.ndarray:
    half = s.rope // 2
    extra = 1.0 / (s.rope_theta ** (np.arange(0, s.rope, 2) / s.rope))
    inter = extra / s.yarn_factor
    low, high = yarn_ramp_dims(s)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half) - low) / (high - low), 0, 1)
    extra_mask = 1.0 - ramp
    return (inter * (1 - extra_mask) + extra * extra_mask).astype(np.float32)


def softmax_scale(s: Spec) -> float:
    scale = (s.nope + s.rope) ** -0.5
    if s.yarn_mscale_all_dim:
        scale *= yarn_mscale(s.yarn_factor, s.yarn_mscale_all_dim) ** 2
    return scale


def _rope(s: Spec, x, pos):
    """x (N, T, heads, R); rotate (x1, x2) halves by pos * inv_freq."""
    half = s.rope // 2
    ang = pos[:, None].astype(jnp.float32) * yarn_inv_freq(s)[None, :]
    m = (yarn_mscale(s.yarn_factor, s.yarn_mscale)
         / yarn_mscale(s.yarn_factor, s.yarn_mscale_all_dim))
    cos = (jnp.cos(ang) * m)[None, :, None]
    sin = (jnp.sin(ang) * m)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# ---------------------------------------------------------------------------
# Forward, layer by layer
# ---------------------------------------------------------------------------
def _rms(s: Spec, x):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + s.eps)


def _mla(s: Spec, w: dict, h, quantize: bool):
    n, t, _ = h.shape
    pos = jnp.arange(t)
    q = _mm("ntr,re->nte", _rms(s, _mm("ntd,dr->ntr", h, w["wq_a"], quantize)),
            w["wq_b"], quantize).reshape(n, t, s.heads, s.nope + s.rope)
    q_nope, q_rope = q[..., :s.nope], _rope(s, q[..., s.nope:], pos)
    kv_a = _mm("ntd,dc->ntc", h, w["wkv_a"], quantize)
    c_kv = _rms(s, kv_a[..., :s.kv_lora])
    k_rope = _rope(s, kv_a[..., None, s.kv_lora:], pos)          # one head
    kv = _mm("ntc,ce->nte", c_kv, w["wkv_b"], quantize).reshape(
        n, t, s.heads, s.nope + s.v_dim)
    k = jnp.concatenate([kv[..., :s.nope], jnp.broadcast_to(
        k_rope, (n, t, s.heads, s.rope))], -1)
    v = kv[..., s.nope:]
    qk = jnp.concatenate([q_nope, q_rope], -1)
    mask = pos[None, :] <= pos[:, None]
    scores = _mm("nqhd,nkhd->nhqk", qk, k, quantize) * softmax_scale(s)
    p = jax.nn.softmax(jnp.where(mask[None, None], scores, -jnp.inf), -1)
    o = _mm("nhqk,nkhd->nqhd", p, v, quantize).reshape(n, t, -1)
    return _mm("nte,ed->ntd", o, w["wo"], quantize)


def _ffn(up, gate, down, h, quantize: bool):
    a = jax.nn.silu(_mm("ntd,df->ntf", h, gate, quantize))
    return _mm("ntf,fd->ntd", a * _mm("ntd,df->ntf", h, up, quantize), down,
               quantize)


def route(s: Spec, lg):
    """Router logits (..., E) -> (weights (..., k), experts (..., k))."""
    probs = jax.nn.softmax(lg, -1)
    groups = probs.reshape(probs.shape[:-1] + (s.n_group, -1))
    _, gidx = jax.lax.top_k(groups.max(-1), s.topk_group)
    kept = jax.nn.one_hot(gidx, s.n_group).sum(-2) > 0
    scores = jnp.where(jnp.repeat(kept, s.router_experts // s.n_group, -1),
                       probs, 0.0)
    wts, idx = jax.lax.top_k(scores, s.top_k)
    if s.norm_topk:
        wts = wts / wts.sum(-1, keepdims=True)
    return wts * s.routed_scale, idx


def _moe(s: Spec, w: dict, h, quantize: bool):
    lg = _mm("ntd,de->nte", h, w["router"], quantize)
    wts, idx = route(s, lg)
    local = idx - s.first_local                                  # (N, T, k)
    y = jnp.zeros_like(h)
    for e in range(s.held):
        gate = jnp.sum(jnp.where(local == e, wts, 0.0), -1)      # (N, T)
        y = y + gate[..., None] * _ffn(w["e_up"][e], w["e_gate"][e],
                                       w["e_down"][e], h, quantize)
    if s.n_shared:
        y = y + _ffn(w["s_up"], w["s_gate"], w["s_down"], h, quantize)
    return y, jnp.sum((local >= 0) & (local < s.held), -1)


@functools.partial(jax.jit, static_argnums=(0, 4, 5))
def layer_forward(s: Spec, key, layer, x, moe: bool, quantize: bool):
    """One block over x (N, T, d) float32 -> (x, held pairs (N, T))."""
    w = layer_weights(s, key, layer, moe)
    x = x + _mla(s, w, _rms(s, x), quantize)
    h = _rms(s, x)
    if not moe:
        return x + _ffn(w["w_up"], w["w_gate"], w["w_down"], h, quantize), \
            jnp.zeros(x.shape[:2], jnp.int32)
    y, held = _moe(s, w, h, quantize)
    return x + y, held


@functools.partial(jax.jit, static_argnums=(0, 3))
def _embed(s: Spec, key, tokens, quantize: bool):
    e = embed_weights(s, key)["embed"][tokens].astype(jnp.float32)
    return _q8(e) if quantize else e


@functools.partial(jax.jit, static_argnums=(0, 3))
def _head(s: Spec, key, x, quantize: bool):
    return _mm("ntd,dv->ntv", _rms(s, x), embed_weights(s, key)["lm_head"],
               quantize)


def forward(s: Spec, key, tokens: np.ndarray, first: int,
            quantize: bool = False) -> tuple[np.ndarray, np.ndarray]:
    """float32 logits (N, T - first, V) at positions first..T-1 of
    ``tokens`` (N, T), and at every position the (token, expert) pairs
    routed to a held expert, summed over the MoE layers (N, T)."""
    x = _embed(s, key, jnp.asarray(tokens), quantize)
    held = jnp.zeros(x.shape[:2], jnp.int32)
    for layer in range(s.layers):
        x, hl = layer_forward(s, key, jnp.int32(layer), x, s.is_moe(layer),
                              quantize)
        held = held + hl
    return (np.asarray(_head(s, key, x[:, first:], quantize)),
            np.asarray(held))


def logits(s: Spec, key, tokens: np.ndarray, first: int,
           quantize: bool = False) -> np.ndarray:
    return forward(s, key, tokens, first, quantize)[0]
