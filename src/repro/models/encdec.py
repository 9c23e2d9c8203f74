"""Encoder-decoder backbone (Whisper-style).

The conv/mel frontend is a STUB per the assignment: ``input_specs`` provides
precomputed frame embeddings (B, S_enc, d_model). The encoder runs the
config's ``encoder_segments`` bidirectionally; the decoder adds cross-
attention (K/V precomputed once from encoder output, cached for decode).
Decoder segments follow the block API of decoder_lm (list-per-layer params,
stacked over count); every decoder layer must be an attention layer (the
cross block reuses its AttnSpec).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, Segment
from repro.models import attention as attn_mod
from repro.models import decoder_lm as dlm
from repro.models.common import apply_norm, cross_entropy, truncnorm_init


def init_params(cfg: ModelConfig, key) -> Any:
    k_enc, k_dec, k_x = jax.random.split(key, 3)
    params = dlm.init_params(cfg, k_dec)
    dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    params["enc_segments"] = []
    keys = jax.random.split(k_enc, max(len(cfg.encoder_segments), 1))
    for i, seg in enumerate(cfg.encoder_segments):
        seg_keys = jax.random.split(keys[i], seg.count)
        if seg.count == 1:
            params["enc_segments"].append(dlm.init_block(seg_keys[0], seg, cfg))
        else:
            params["enc_segments"].append(
                jax.vmap(lambda k, _s=seg: dlm.init_block(k, _s, cfg))(seg_keys))
    params["enc_norm"] = dlm._norm_params(cfg, cfg.d_model)
    # one cross-attention block per decoder layer (stacked per segment)
    params["cross"] = []
    xkeys = jax.random.split(k_x, max(len(cfg.segments), 1))
    for i, seg in enumerate(cfg.segments):
        seg_keys = jax.random.split(xkeys[i], seg.count)

        def one_block(k, _seg=seg):
            kk = jax.random.split(k, len(_seg.layers))
            return [{"norm": dlm._norm_params(cfg, cfg.d_model),
                     "attn": attn_mod.init_attn(kk[j], l.attn, cfg.d_model, dt)}
                    for j, l in enumerate(_seg.layers)]

        if seg.count == 1:
            params["cross"].append(one_block(seg_keys[0]))
        else:
            params["cross"].append(jax.vmap(one_block)(seg_keys))
    return params


def encode(params, cfg: ModelConfig, frames):
    """frames: (B, S_enc, d_model) precomputed frontend embeddings."""
    b, s, _ = frames.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    x = frames
    for seg, seg_p in zip(cfg.encoder_segments, params["enc_segments"]):
        if seg.count == 1:
            x, _, _, _ = dlm.block_full(seg_p, seg, cfg, x, positions, False,
                                        s)
        else:
            def body(h, p_i, _seg=seg):
                h2, _, _, _ = dlm.block_full(p_i, _seg, cfg, h, positions,
                                             False, s)
                return h2, None

            x, _ = jax.lax.scan(dlm._maybe_remat(body, cfg), x, seg_p)
    return apply_norm(params["enc_norm"], x, cfg.norm)


def _cross_kv(params, cfg: ModelConfig, enc_out):
    """Precompute cross-attention K/V per decoder layer (the decode cache)."""
    kvs = []
    b, t, _ = enc_out.shape
    for seg, xp in zip(cfg.segments, params["cross"]):

        def one_block(block_p, _seg=seg):
            out = []
            for p, l in zip(block_p, _seg.layers):
                spec = l.attn
                k = (enc_out @ p["attn"]["wk"]).reshape(b, t, spec.n_kv_heads,
                                                        spec.head_dim)
                v = (enc_out @ p["attn"]["wv"]).reshape(b, t, spec.n_kv_heads,
                                                        spec.head_dim)
                out.append((k, v))
            return out

        if seg.count == 1:
            kvs.append(one_block(xp))
        else:
            kvs.append(jax.vmap(one_block)(xp))
    return kvs


def _dec_block_full(block_p, block_x, block_kv, seg: Segment,
                    cfg: ModelConfig, x, positions, want_cache, max_len):
    """Self-attn layer + cross-attn per layer in the block."""
    caches = []
    for p_i, xp_i, kv_i, layer in zip(block_p, block_x, block_kv, seg.layers):
        x, _, cache, _ = dlm.layer_full(p_i, layer, cfg, x, positions,
                                        want_cache, max_len)
        h = apply_norm(xp_i["norm"], x, cfg.norm)
        x = x + attn_mod.attn_cross(xp_i["attn"], layer.attn, h, kv_i)
        caches.append(cache)
    return x, caches


def _decoder(params, cfg: ModelConfig, tokens, enc_out, want_cache=False,
             max_len=0, cross_kv=None):
    x = params["embed"][tokens]
    b, s = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    max_len = max_len or s
    if cross_kv is None:
        cross_kv = _cross_kv(params, cfg, enc_out)
    caches = []
    for seg, seg_p, xp, kv in zip(cfg.segments, params["segments"],
                                  params["cross"], cross_kv):
        if seg.count == 1:
            x, cache = _dec_block_full(seg_p, xp, kv, seg, cfg, x, positions,
                                       want_cache, max_len)
            caches.append(cache)
        else:
            def body(h_in, pc, _seg=seg):
                p_i, xp_i, kv_i = pc
                h2, cache_i = _dec_block_full(p_i, xp_i, kv_i, _seg, cfg,
                                              h_in, positions, want_cache,
                                              max_len)
                return h2, cache_i

            x, seg_caches = jax.lax.scan(dlm._maybe_remat(body, cfg), x,
                                         (seg_p, xp, kv))
            caches.append(seg_caches)
    x = apply_norm(params["final_norm"], x, cfg.norm)
    return dlm._logits(params, cfg, x), caches


def loss_and_metrics(params, cfg: ModelConfig, batch: dict):
    """batch: frames (B,S_enc,d), tokens (B,S_dec), labels (B,S_dec)."""
    enc_out = encode(params, cfg, batch["frames"])
    logits, _ = _decoder(params, cfg, batch["tokens"], enc_out)
    labels = batch["labels"]
    mask = (labels >= 0).astype(jnp.float32)
    ce = cross_entropy(logits, jnp.maximum(labels, 0), mask)
    return ce, {"loss": ce, "ce": ce, "aux": jnp.zeros((), jnp.float32)}


def prefill(params, cfg: ModelConfig, frames, tokens, max_len: int = 0):
    enc_out = encode(params, cfg, frames)
    cross_kv = _cross_kv(params, cfg, enc_out)
    logits, caches = _decoder(params, cfg, tokens, enc_out, want_cache=True,
                              max_len=max_len, cross_kv=cross_kv)
    return logits[:, -1:], {"self": caches, "cross": cross_kv}


def decode_step(params, cfg: ModelConfig, token, pos, caches):
    x = params["embed"][token]
    new_self = []
    for seg, seg_p, xp, kv, seg_c in zip(cfg.segments, params["segments"],
                                         params["cross"], caches["cross"],
                                         caches["self"]):

        def block_step(p_b, xp_b, kv_b, c_b, h, _seg=seg):
            new_c = []
            for p_i, xp_i, kv_i, c_i, layer in zip(p_b, xp_b, kv_b, c_b,
                                                   _seg.layers):
                h, c2, _ = dlm.layer_decode(p_i, layer, cfg, h, pos, c_i)
                hc = apply_norm(xp_i["norm"], h, cfg.norm)
                h = h + attn_mod.attn_cross(xp_i["attn"], layer.attn, hc, kv_i)
                new_c.append(c2)
            return h, new_c

        if seg.count == 1:
            x, c = block_step(seg_p, xp, kv, seg_c, x)
            new_self.append(c)
        else:
            def body(h_in, pc, _seg=seg):
                p_i, xp_i, kv_i, c_i = pc
                h2, c2 = block_step(p_i, xp_i, kv_i, c_i, h_in)
                return h2, c2

            x, seg_new = jax.lax.scan(body, x, (seg_p, xp, kv, seg_c))
            new_self.append(seg_new)
    x = apply_norm(params["final_norm"], x, cfg.norm)
    return dlm._logits(params, cfg, x), {"self": new_self,
                                         "cross": caches["cross"]}
