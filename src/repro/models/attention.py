"""Attention layers: GQA (with sliding window, qk-norm) and DeepSeek-style MLA.

Two execution paths per layer:
  * full-sequence (train / prefill) — optionally routed through the Pallas
    flash-attention kernel (FLAGS["use_flash"], TPU target);
  * single-token decode against a KV cache — full cache, ring (sliding-window)
    cache, or MLA compressed cache (absorbed matmul order). Decode caches
    hold their slots minor, the layout the decode kernel reads in place.

Shapes: x (B, S, d_model); caches live in a dict pytree so they pjit-shard
with NamedSharding like any other state.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import AttnSpec, MLASpec
from repro.models import common as cc
from repro.models.common import (apply_norm, apply_rope, causal_mask,
                                 dense_init, logical_constraint)

from repro.models.common import RUNTIME as FLAGS  # launcher-set knobs


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------
def init_attn(key, spec: AttnSpec, d_model: int, dtype) -> dict:
    ks = jax.random.split(key, 4)
    h, kv, dh = spec.n_heads, spec.n_kv_heads, spec.head_dim
    p = {
        "wq": dense_init(ks[0], d_model, h * dh, dtype),
        "wk": dense_init(ks[1], d_model, kv * dh, dtype),
        "wv": dense_init(ks[2], d_model, kv * dh, dtype),
        "wo": dense_init(ks[3], h * dh, d_model, dtype),
    }
    if spec.qk_norm:
        p["q_norm"] = {"scale": jnp.ones((dh,), jnp.float32)}
        p["k_norm"] = {"scale": jnp.ones((dh,), jnp.float32)}
    return p


def _project_qkv(p, spec: AttnSpec, x, positions):
    b, s, _ = x.shape
    h, kv, dh = spec.n_heads, spec.n_kv_heads, spec.head_dim
    q = (x @ p["wq"]).reshape(b, s, h, dh)
    k = (x @ p["wk"]).reshape(b, s, kv, dh)
    v = (x @ p["wv"]).reshape(b, s, kv, dh)
    if spec.qk_norm:
        q = apply_norm(p["q_norm"], q, "rmsnorm")
        k = apply_norm(p["k_norm"], k, "rmsnorm")
    if spec.use_rope:
        q = apply_rope(q, positions, spec.rope_theta)
        k = apply_rope(k, positions, spec.rope_theta)
    return q, k, v


def _gqa_attend(q, k, v, mask, kv: str = "btkd"):
    """q: (B,S,H,D) k/v: (B,T,KV,D), or in the axis order ``kv`` names
    (``"bkdt"``: a decode cache, slots minor); grouped einsum, no KV
    repetition."""
    b, s, h, dh = q.shape
    kvh = k.shape[kv.index("k")]
    g = h // kvh
    qg = q.reshape(b, s, kvh, g, dh)
    scores = jnp.einsum(f"bskgd,{kv}->bksgt", qg, k).astype(jnp.float32)
    scores *= dh ** -0.5
    scores = jnp.where(mask[:, None, :, None, :] if mask.ndim == 3
                       else mask[None, None, :, None, :], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum(f"bksgt,{kv}->bskgd", w, v)
    return out.reshape(b, s, h, dh)


def _chunked_attend(q, k, v, spec: AttnSpec, q_chunk: int):
    """Flash-style q-block attention in pure XLA — the shardable form for
    SPMD lowering: q keeps full heads (shardable on `model` even when
    n_kv_heads < axis size), kv heads are repeated *after* sharding
    propagation (a per-shard slice, not a materialized copy), and the
    (bq, T) score tile is the only quadratic live tensor. The chunk body is
    rematerialized so backward residuals stay one tile big.

    q: (B, S, H, D); k/v: (B, T, KV, D). S % q_chunk == 0 (callers pad)."""
    b, s, h, dh = q.shape
    t, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    kr = jnp.repeat(k, g, axis=2)                   # (B, T, H, D)
    vr = jnp.repeat(v, g, axis=2)
    nq = s // q_chunk
    qb = q.reshape(b, nq, q_chunk, h, dh).transpose(1, 0, 2, 3, 4)
    kpos = jnp.arange(t, dtype=jnp.int32)

    def body(idx_qblk):
        idx, q_blk = idx_qblk                       # q_blk (B, bq, H, D)
        qpos = idx * q_chunk + jnp.arange(q_chunk, dtype=jnp.int32)
        scores = jnp.einsum("bqhd,bthd->bhqt", q_blk, kr,
                            preferred_element_type=jnp.float32)
        scores = scores * dh ** -0.5
        if spec.causal:
            m = kpos[None, :] <= qpos[:, None]
            if spec.window is not None:
                m &= kpos[None, :] > (qpos[:, None] - spec.window)
            scores = jnp.where(m[None, None], scores, -1e30)
        w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        return jnp.einsum("bhqt,bthd->bqhd", w, vr)

    out = jax.lax.map(jax.checkpoint(body),
                      (jnp.arange(nq, dtype=jnp.int32), qb))
    return out.transpose(1, 0, 2, 3, 4).reshape(b, s, h, dh)


def attn_full(p, spec: AttnSpec, x, positions, return_kv: bool = False):
    """Training / prefill self-attention (causal unless spec.causal=False)."""
    b, s, _ = x.shape
    q, k, v = _project_qkv(p, spec, x, positions)
    q = logical_constraint(q, cc.BATCH, None, cc.HEADS, None)
    k = logical_constraint(k, cc.BATCH, None, cc.HEADS, None)
    v = logical_constraint(v, cc.BATCH, None, cc.HEADS, None)
    q_chunk = FLAGS["q_chunk"]
    if FLAGS["use_flash"] and spec.causal:
        from repro.kernels.flash_attention import ops as flash_ops
        out = flash_ops.flash_attention(q, k, v, window=spec.window)
    elif q_chunk and s % q_chunk == 0 and s > q_chunk:
        out = _chunked_attend(q, k, v, spec, q_chunk)
    else:
        if spec.causal:
            mask = causal_mask(positions, positions, spec.window)
        else:
            mask = jnp.ones((s, s), bool) if positions.ndim == 1 else \
                jnp.ones((b, s, s), bool)
        out = _gqa_attend(q, k, v, mask)
    y = out.reshape(b, s, -1) @ p["wo"]
    y = logical_constraint(y, cc.BATCH, cc.SEQ, cc.EMBED)
    if return_kv:
        return y, (k, v)
    return y


def attn_cross(p, spec: AttnSpec, x, kv_cache: tuple):
    """Cross-attention (whisper decoder): K,V precomputed from the encoder."""
    b, s, _ = x.shape
    h, dh = spec.n_heads, spec.head_dim
    q = (x @ p["wq"]).reshape(b, s, h, dh)
    k, v = kv_cache
    mask = jnp.ones((b, s, k.shape[1]), bool)
    out = _gqa_attend(q, k, v, mask)
    return out.reshape(b, s, -1) @ p["wo"]


# -- KV caches ---------------------------------------------------------------
# Decode caches hold their slots minor, (B, KV, D, T), stacked (L, B, KV, D, T)
# in a scanned segment: the layout the decode kernel's blocks read, so it
# reads a layer's K/V where they lie. Slot counts above 128 are rounded up to
# a multiple of 128 (the kernel's blocks divide them); the extra slots are
# never valid.
def cache_slots(n: int) -> int:
    """Slots of a decode cache for ``n`` positions."""
    return n if n <= 128 else -(-n // 128) * 128


def init_cache(spec: AttnSpec, batch: int, max_len: int, dtype) -> dict:
    """Full cache, or ring cache bounded at the sliding window; k/v
    (B, KV, D, T), slots minor."""
    t = cache_slots(max_len if spec.window is None
                    else min(spec.window, max_len))
    kv, dh = spec.n_kv_heads, spec.head_dim
    cache = {
        "k": jnp.zeros((batch, kv, dh, t), dtype),
        "v": jnp.zeros((batch, kv, dh, t), dtype),
    }
    if spec.window is not None:
        # per-slot absolute positions (-1 = empty)
        cache["slot_pos"] = jnp.full((t,), -1, jnp.int32)
    return cache


def attn_prefill(p, spec: AttnSpec, x, positions, max_len: int):
    """Full forward that also fills the decode cache. Assumes positions are
    0..S-1 (no padding). Returns (y, cache)."""
    b, s, _ = x.shape
    y, (k, v) = attn_full(p, spec, x, positions, return_kv=True)
    k, v = k.transpose(0, 2, 3, 1), v.transpose(0, 2, 3, 1)   # (B,KV,D,S)
    cache = init_cache(spec, b, max_len, x.dtype)
    if spec.window is None:
        cache["k"] = jax.lax.dynamic_update_slice(cache["k"], k, (0, 0, 0, 0))
        cache["v"] = jax.lax.dynamic_update_slice(cache["v"], v, (0, 0, 0, 0))
    else:
        # last min(S, T) tokens land in their ring slots
        w = cache["k"].shape[-1]
        take = min(s, w)
        idx = jnp.arange(s - take, s, dtype=jnp.int32)       # absolute positions
        slots = jnp.mod(idx, w)
        cache["k"] = cache["k"].at[..., slots].set(k[..., s - take:])
        cache["v"] = cache["v"].at[..., slots].set(v[..., s - take:])
        cache["slot_pos"] = cache["slot_pos"].at[slots].set(idx)
    return y, cache


def cache_slab(buf, layer):
    """``buf`` itself, or with ``layer`` its slab of a layer-stacked cache
    (leading ``(count,)`` axis)."""
    if layer is None:
        return buf
    return jax.lax.dynamic_index_in_dim(buf, layer, 0, keepdims=False)


def cache_put(buf, new, start: tuple, layer):
    """``dynamic_update_slice`` of ``new`` at ``start``; with ``layer`` into
    that layer's slab of a layer-stacked ``buf``, so only ``new``'s bytes
    move."""
    if layer is None:
        return jax.lax.dynamic_update_slice(buf, new, start)
    return jax.lax.dynamic_update_slice(buf, new[None], (layer,) + start)


def attn_decode(p, spec: AttnSpec, x, pos, cache: dict, layer=None):
    """One-token decode. x: (B,1,d); pos: scalar int32 (current position).
    Returns (y, new_cache).

    ``layer`` (int32 scalar) says ``cache`` is a segment's layer-stacked
    cache, each leaf with a leading (count,) axis, and this is its layer
    ``layer``: the new K/V slot column (and ring slot position) is written
    in place into the stacked arrays, the decode kernel reads the layer's
    K/V from them by index, and the returned cache keeps the stacked shape.
    ``None`` is one layer's unstacked cache. k/v are (B, KV, D, T)."""
    b = x.shape[0]
    positions = jnp.full((b, 1), pos, jnp.int32)
    q, k_new, v_new = _project_qkv(p, spec, x, positions)

    t = cache["k"].shape[-1]
    slot = pos if spec.window is None else jnp.mod(pos, t)
    new_cache = {   # the new row as one slot column (B, KV, D, 1)
        "k": cache_put(cache["k"], k_new.transpose(0, 2, 3, 1),
                       (0, 0, 0, slot), layer),
        "v": cache_put(cache["v"], v_new.transpose(0, 2, 3, 1),
                       (0, 0, 0, slot), layer),
    }
    if spec.window is None:
        valid = jnp.arange(t, dtype=jnp.int32) <= pos
    else:
        new_cache["slot_pos"] = cache_put(
            cache["slot_pos"], jnp.full((1,), pos, jnp.int32), (slot,), layer)
        slot_pos = cache_slab(new_cache["slot_pos"], layer)
        valid = (slot_pos >= 0) & (slot_pos <= pos) & (slot_pos > pos - spec.window)

    if FLAGS["use_flash"]:
        from repro.kernels.decode_attention import ops as dec_ops
        out = dec_ops.decode_attention(q, new_cache["k"], new_cache["v"],
                                       valid, layer)
    else:
        mask = valid[None, None, :]  # (1,1,T) broadcast over batch, q=1
        out = _gqa_attend(q, cache_slab(new_cache["k"], layer),
                          cache_slab(new_cache["v"], layer), mask, kv="bkdt")
    y = out.reshape(b, 1, -1) @ p["wo"]
    return y, new_cache


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2): low-rank Q and compressed KV with decoupled RoPE.
#
# The cache holds one latent row per token, c_kv ‖ k_rope (kv_lora_rank +
# qk_rope_dim), as one kv head with its slots minor, (B, 1, L + R, T).
# Prefill expands it per head and, under use_flash, runs the flash kernel
# over q_nope ‖ q_rope against k_nope ‖ k_rope with v padded to the same
# width. Absorbed decode folds wkv_b's key half into the query and
# its value half into the output, so the decode kernel attends 128 query
# heads against the single latent head: K = V = the latent row, and the
# first kv_lora_rank output dims are sum_t w_t c_kv[t].
# ---------------------------------------------------------------------------
def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature factor (1 without scaling)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_ramp(spec: MLASpec) -> tuple[int, int]:
    """Rope pair indices where YaRN's ramp from extrapolated (below) to
    interpolated (above) frequencies starts and ends."""
    dim, base = spec.qk_rope_dim, spec.rope_theta

    def corr(rotations):
        return (dim * math.log(spec.yarn_original_max_pos
                               / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = math.floor(corr(spec.yarn_beta_fast))
    high = math.ceil(corr(spec.yarn_beta_slow))
    return max(low, 0), min(high, dim - 1)


def mla_rope_freqs(spec: MLASpec):
    """Inverse frequencies of the rope dims (qk_rope_dim // 2,), YaRN's
    blend of theta's and theta's / factor; None without YaRN."""
    if spec.yarn_factor <= 1:
        return None
    half = spec.qk_rope_dim // 2
    extra = 1.0 / (spec.rope_theta
                   ** (np.arange(half, dtype=np.float32) / half))
    inter = extra / np.float32(spec.yarn_factor)
    low, high = yarn_ramp(spec)
    ramp = np.clip((np.arange(half, dtype=np.float32) - low)
                   / ((high - low) or 0.001), 0, 1)
    return jnp.asarray(inter * ramp + extra * (1 - ramp), jnp.float32)


def mla_softmax_scale(spec: MLASpec) -> float:
    scale = (spec.qk_nope_dim + spec.qk_rope_dim) ** -0.5
    if spec.yarn_mscale_all_dim:
        scale *= yarn_mscale(spec.yarn_factor, spec.yarn_mscale_all_dim) ** 2
    return scale


def _mla_rope(spec: MLASpec, x, positions):
    out = apply_rope(x, positions, spec.rope_theta, mla_rope_freqs(spec))
    if spec.yarn_factor > 1 and spec.yarn_mscale_all_dim:
        cs = (yarn_mscale(spec.yarn_factor, spec.yarn_mscale)
              / yarn_mscale(spec.yarn_factor, spec.yarn_mscale_all_dim))
        if cs != 1.0:
            out = (out.astype(jnp.float32) * cs).astype(x.dtype)
    return out


def init_mla(key, spec: MLASpec, d_model: int, dtype) -> dict:
    ks = jax.random.split(key, 5)
    h = spec.n_heads
    qd = spec.qk_nope_dim + spec.qk_rope_dim
    return {
        "wq_a": dense_init(ks[0], d_model, spec.q_lora_rank, dtype),
        "q_norm": {"scale": jnp.ones((spec.q_lora_rank,), jnp.float32)},
        "wq_b": dense_init(ks[1], spec.q_lora_rank, h * qd, dtype),
        "wkv_a": dense_init(ks[2], d_model,
                            spec.kv_lora_rank + spec.qk_rope_dim, dtype),
        "kv_norm": {"scale": jnp.ones((spec.kv_lora_rank,), jnp.float32)},
        "wkv_b": dense_init(ks[3], spec.kv_lora_rank,
                            h * (spec.qk_nope_dim + spec.v_head_dim), dtype),
        "wo": dense_init(ks[4], h * spec.v_head_dim, d_model, dtype),
    }


def _mla_q(p, spec: MLASpec, x, positions):
    b, s, _ = x.shape
    h = spec.n_heads
    q = apply_norm(p["q_norm"], x @ p["wq_a"], "rmsnorm") @ p["wq_b"]
    q = q.reshape(b, s, h, spec.qk_nope_dim + spec.qk_rope_dim)
    q_nope, q_rope = jnp.split(q, [spec.qk_nope_dim], axis=-1)
    return q_nope, _mla_rope(spec, q_rope, positions)


def _mla_latent(p, spec: MLASpec, x, positions):
    """The cache row of each token, (B, S, L + R): normalized compressed kv
    ‖ rotated shared k_rope."""
    kv_a = x @ p["wkv_a"]
    c_kv, k_rope = jnp.split(kv_a, [spec.kv_lora_rank], axis=-1)
    c_kv = apply_norm(p["kv_norm"], c_kv, "rmsnorm")          # (B,S,L)
    k_rope = _mla_rope(spec, k_rope[:, :, None, :], positions)[:, :, 0, :]
    return jnp.concatenate([c_kv, k_rope.astype(c_kv.dtype)], axis=-1)


def _mla_chunked(q_nope, q_rope, k_nope, k_rope, v, scale, q_chunk: int,
                 dtype):
    """q-block chunked MLA attention (same memory argument as
    _chunked_attend; k_rope is shared across heads so it never repeats)."""
    b, s, h, dn = q_nope.shape
    t = k_nope.shape[1]
    nq = s // q_chunk
    qn = q_nope.reshape(b, nq, q_chunk, h, dn).transpose(1, 0, 2, 3, 4)
    qr = q_rope.reshape(b, nq, q_chunk, h, -1).transpose(1, 0, 2, 3, 4)
    kpos = jnp.arange(t, dtype=jnp.int32)

    def body(args):
        idx, qn_blk, qr_blk = args
        qpos = idx * q_chunk + jnp.arange(q_chunk, dtype=jnp.int32)
        scores = (jnp.einsum("bqhn,bthn->bhqt", qn_blk, k_nope,
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("bqhr,btr->bhqt", qr_blk, k_rope,
                               preferred_element_type=jnp.float32)) * scale
        m = kpos[None, :] <= qpos[:, None]
        scores = jnp.where(m[None, None], scores, -1e30)
        w = jax.nn.softmax(scores, axis=-1).astype(dtype)
        return jnp.einsum("bhqt,bthd->bqhd", w, v)

    out = jax.lax.map(jax.checkpoint(body),
                      (jnp.arange(nq, dtype=jnp.int32), qn, qr))
    return out.transpose(1, 0, 2, 3, 4).reshape(b, s, -1)


def _mla_flash(q_nope, q_rope, k_nope, k_rope, v, scale):
    """Causal MLA through the flash kernel: every head attends with
    q_nope ‖ q_rope against k_nope ‖ k_rope (k_rope broadcast over heads);
    q, k and v are zero-padded to one width, the output sliced back."""
    from repro.kernels.flash_attention import ops as flash_ops
    b, s, h, _ = q_nope.shape
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_rope[:, :, None, :].astype(k_nope.dtype),
        (b, s, h, k_rope.shape[-1]))], axis=-1)
    dv = v.shape[-1]
    d = max(q.shape[-1], dv)

    def pad(a):
        return jnp.pad(a, ((0, 0),) * 3 + ((0, d - a.shape[-1]),))

    out = flash_ops.flash_attention(pad(q), pad(k), pad(v), scale=scale)
    return out[..., :dv].reshape(b, s, -1)


def mla_full(p, spec: MLASpec, x, positions, return_latent: bool = False):
    """Training / prefill MLA; with ``return_latent`` also the cache rows
    (B, S, L + R)."""
    b, s, _ = x.shape
    h = spec.n_heads
    q_nope, q_rope = _mla_q(p, spec, x, positions)
    latent = _mla_latent(p, spec, x, positions)
    c_kv, k_rope = jnp.split(latent, [spec.kv_lora_rank], axis=-1)
    kv = (c_kv @ p["wkv_b"]).reshape(b, s, h, spec.qk_nope_dim + spec.v_head_dim)
    k_nope, v = jnp.split(kv, [spec.qk_nope_dim], axis=-1)
    k_nope = logical_constraint(k_nope, cc.BATCH, None, cc.HEADS, None)
    v = logical_constraint(v, cc.BATCH, None, cc.HEADS, None)
    scale = mla_softmax_scale(spec)
    q_chunk = FLAGS["q_chunk"]
    if FLAGS["use_flash"]:
        out = _mla_flash(q_nope, q_rope, k_nope, k_rope, v, scale)
    elif q_chunk and s % q_chunk == 0 and s > q_chunk:
        out = _mla_chunked(q_nope, q_rope, k_nope, k_rope, v, scale, q_chunk,
                           x.dtype)
    else:
        scores = (jnp.einsum("bshd,bthd->bhst", q_nope, k_nope)
                  + jnp.einsum("bshr,btr->bhst", q_rope, k_rope)
                  ).astype(jnp.float32)
        scores *= scale
        mask = causal_mask(positions, positions)
        scores = jnp.where(mask[:, None] if mask.ndim == 3
                           else mask[None, None], scores, -1e30)
        w = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
        out = jnp.einsum("bhst,bthd->bshd", w, v).reshape(b, s, -1)
    y = out @ p["wo"]
    return (y, latent) if return_latent else y


def init_mla_cache(spec: MLASpec, batch: int, max_len: int, dtype) -> dict:
    """The MLA win: cache only (kv_lora_rank + rope_dim) per token, held as
    one kv head with its slots minor, (B, 1, L + R, T)."""
    return {"latent": jnp.zeros(
        (batch, 1, spec.kv_lora_rank + spec.qk_rope_dim,
         cache_slots(max_len)), dtype)}


def mla_prefill(p, spec: MLASpec, x, positions, max_len: int):
    b, s, _ = x.shape
    y, latent = mla_full(p, spec, x, positions, return_latent=True)
    cache = init_mla_cache(spec, b, max_len, x.dtype)
    cache["latent"] = jax.lax.dynamic_update_slice(
        cache["latent"], latent.astype(x.dtype).transpose(0, 2, 1)[:, None],
        (0, 0, 0, 0))
    return y, cache


def mla_decode(p, spec: MLASpec, x, pos, cache: dict, layer=None):
    """One-token MLA decode in the matmul-absorbed order: wkv_b's key half
    is folded into the query and its value half into the output, so K/V are
    never re-expanded for the whole cache; the attention runs through the
    decode kernel under use_flash, with the latent cache as K = V. ``layer``
    is as in ``attn_decode``: with it, the new latent slot column is written
    in place into the layer-stacked cache and read from it there."""
    b = x.shape[0]
    h, lr = spec.n_heads, spec.kv_lora_rank
    positions = jnp.full((b, 1), pos, jnp.int32)
    q_nope, q_rope = _mla_q(p, spec, x, positions)            # (B,1,H,*)
    row = _mla_latent(p, spec, x, positions)                  # (B,1,L+R)
    new_cache = {"latent": cache_put(cache["latent"],
                                     row.transpose(0, 2, 1)[:, None],
                                     (0, 0, 0, pos), layer)}

    t = new_cache["latent"].shape[-1]
    valid = jnp.arange(t, dtype=jnp.int32) <= pos
    scale = mla_softmax_scale(spec)
    wkv_b = p["wkv_b"].reshape(lr, h, spec.qk_nope_dim + spec.v_head_dim)
    w_k = wkv_b[..., :spec.qk_nope_dim]    # (L,H,N)
    w_v = wkv_b[..., spec.qk_nope_dim:]    # (L,H,V)

    with jax.named_scope("mla_absorb"):
        q_eff = jnp.einsum("bqhn,lhn->bqhl", q_nope, w_k)     # (B,1,H,L)
        q_cat = jnp.concatenate([q_eff, q_rope.astype(q_eff.dtype)],
                                axis=-1)                       # (B,1,H,L+R)
    if FLAGS["use_flash"]:
        from repro.kernels.decode_attention import ops as dec_ops
        ctx = dec_ops.decode_attention(q_cat, new_cache["latent"],
                                       new_cache["latent"], valid, layer,
                                       scale=scale)
        ctx = ctx[..., :lr]
    else:
        latent = cache_slab(new_cache["latent"], layer)[:, 0]  # (B,L+R,T)
        scores = jnp.einsum("bqhc,bct->bhqt", q_cat, latent)
        scores = scores.astype(jnp.float32) * scale
        scores = jnp.where(valid[None, None, None, :], scores, -1e30)
        w = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
        ctx = jnp.einsum("bhqt,blt->bqhl", w, latent[:, :lr])
    with jax.named_scope("mla_absorb"):
        out = jnp.einsum("bqhl,lhv->bqhv", ctx.astype(x.dtype), w_v)
    y = out.reshape(b, 1, -1) @ p["wo"]
    return y, new_cache
