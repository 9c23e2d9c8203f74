"""Generic decoder-only LM over config segments.

A segment is ``count`` repetitions of a *block* of layers (possibly
heterogeneous — e.g. Jamba's 7 Mamba + 1 attention, Gemma-3's 5 local +
1 global). Segments with count > 1 run under ``jax.lax.scan`` with stacked
parameters and per-block remat — HLO size and compile time stay flat in depth
(the 512-device dry-runs rely on this). Three entry points:

  * ``loss_and_metrics``    — training objective (CE + MoE aux)
  * ``prefill``             — forward pass that also fills decode caches
  * ``decode_step``         — one token against the caches

Segment parameters are a list (one entry per layer-in-block) of layer param
dicts; for count > 1 every leaf gains a leading (count,) axis. Caches mirror
that layout, so they shard with NamedSharding like parameters. Decode scans a
count > 1 segment with its stacked caches in the scan's carry and the layer
index beside the stacked params in its xs: an attention or MLA layer writes
its new slot column in place into the stacked arrays, held slots minor, and
the decode kernel reads the layer's K/V from them by that index; a recurrent
layer writes its new state slab back. So no layer's cache is sliced out and
restacked whole. The carry keeps the layout the caches enter the step in, so
the donated cache is updated where it lies.

A served MoE layer (``mlp.moe_dropless``) also returns counts of its work,
int32 scalars; the layer and block functions return them beside the caches
(empty for every other layer) and ``prefill`` / ``decode_step`` sum them
over the layers when asked (``counts=True``).
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from jax.ad_checkpoint import checkpoint_name
from jax.experimental.layout import Layout, with_layout_constraint

from repro.configs.base import LayerSpec, ModelConfig, Segment
from repro.models import attention as attn_mod
from repro.models import common as cc
from repro.models import mlp as mlp_mod
from repro.models import ssm as ssm_mod
from repro.models import xlstm as xlstm_mod
from repro.models.common import (apply_norm, cross_entropy, logical_constraint,
                                 rmsnorm_params, layernorm_params,
                                 truncnorm_init)

PyTree = Any


def _norm_params(cfg: ModelConfig, d: int):
    return layernorm_params(d) if cfg.norm == "layernorm" else rmsnorm_params(d)


def _dtype(cfg: ModelConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


# ---------------------------------------------------------------------------
# Single layer
# ---------------------------------------------------------------------------
def init_layer(key, layer: LayerSpec, cfg: ModelConfig) -> dict:
    ks = jax.random.split(key, 3)
    dt = _dtype(cfg)
    d = cfg.d_model
    p: dict = {"norm1": _norm_params(cfg, d)}
    if layer.kind == "attn":
        p["attn"] = attn_mod.init_attn(ks[0], layer.attn, d, dt)
    elif layer.kind == "mla":
        p["mla"] = attn_mod.init_mla(ks[0], layer.mla, d, dt)
    elif layer.kind == "mamba":
        p["mamba"] = ssm_mod.init_mamba(ks[0], layer.mamba, d, dt)
    elif layer.kind == "mlstm":
        p["mlstm"] = xlstm_mod.init_mlstm(ks[0], layer.xlstm, d, dt)
    elif layer.kind == "slstm":
        p["slstm"] = xlstm_mod.init_slstm(ks[0], layer.xlstm, d, dt)
    else:
        raise ValueError(layer.kind)
    if layer.mlp == "dense":
        p["norm2"] = _norm_params(cfg, d)
        p["mlp"] = mlp_mod.init_mlp(ks[1], d, layer.d_ff, cfg.act, dt)
    elif layer.mlp == "moe":
        p["norm2"] = _norm_params(cfg, d)
        p["moe"] = mlp_mod.init_moe(ks[1], layer.moe, d, cfg.act, dt)
    return p


def init_block(key, seg: Segment, cfg: ModelConfig) -> list:
    keys = jax.random.split(key, len(seg.layers))
    return [init_layer(k, l, cfg) for k, l in zip(keys, seg.layers)]


def layer_cache_init(layer: LayerSpec, cfg: ModelConfig, batch: int,
                     max_len: int) -> Optional[dict]:
    dt = _dtype(cfg)
    if layer.kind == "attn":
        return attn_mod.init_cache(layer.attn, batch, max_len, dt)
    if layer.kind == "mla":
        return attn_mod.init_mla_cache(layer.mla, batch, max_len, dt)
    if layer.kind == "mamba":
        return ssm_mod.init_mamba_cache(layer.mamba, cfg.d_model, batch, dt)
    if layer.kind == "mlstm":
        return xlstm_mod.init_mlstm_cache(layer.xlstm, cfg.d_model, batch, dt)
    if layer.kind == "slstm":
        return xlstm_mod.init_slstm_state(layer.xlstm, cfg.d_model, batch)
    raise ValueError(layer.kind)


def has_moe(cfg: ModelConfig) -> bool:
    """Whether any layer of ``cfg`` is an MoE layer."""
    return any(l.mlp == "moe" for seg in cfg.segments for l in seg.layers)


def _add_counts(a: dict, b: dict) -> dict:
    """Two layers' counts summed key by key."""
    return {k: a.get(k, 0) + b.get(k, 0) for k in sorted(a.keys() | b.keys())}


def _sum_layers(counts: dict) -> dict:
    """Counts of a scanned segment, stacked over its repetitions, summed."""
    return {k: jnp.sum(v, axis=0) for k, v in counts.items()}


def layer_full(p, layer: LayerSpec, cfg: ModelConfig, x, positions,
               want_cache: bool, max_len: int):
    """Full-sequence layer. Returns (x, aux, cache_or_None, counts);
    ``counts`` is a served MoE layer's (with ``want_cache``), else empty."""
    with jax.named_scope("norm"):
        h = apply_norm(p["norm1"], x, cfg.norm)
        # Pin the sequence-parallel boundary to the (low-precision) norm
        # OUTPUT: without this, GSPMD hoists the seq all-gather above the
        # norm's f32 upcast and the boundary collective moves 2x the bytes
        # (SSPerf H1).
        h = logical_constraint(h, cc.BATCH, cc.SEQ, cc.EMBED)
    cache, counts = None, {}
    with jax.named_scope("attn"):
        if layer.kind == "attn":
            if want_cache:
                y, cache = attn_mod.attn_prefill(p["attn"], layer.attn, h,
                                                 positions, max_len)
            else:
                y = attn_mod.attn_full(p["attn"], layer.attn, h, positions)
        elif layer.kind == "mla":
            if want_cache:
                y, cache = attn_mod.mla_prefill(p["mla"], layer.mla, h,
                                                positions, max_len)
            else:
                y = attn_mod.mla_full(p["mla"], layer.mla, h, positions)
        elif layer.kind == "mamba":
            if want_cache:
                y, cache = ssm_mod.mamba_prefill(p["mamba"], layer.mamba, h)
            else:
                y = ssm_mod.mamba_full(p["mamba"], layer.mamba, h)
        elif layer.kind == "mlstm":
            if want_cache:
                y, cache = xlstm_mod.mlstm_prefill(p["mlstm"], layer.xlstm, h)
            else:
                y = xlstm_mod.mlstm_full(p["mlstm"], layer.xlstm, h)
        elif layer.kind == "slstm":
            if want_cache:
                y, cache = xlstm_mod.slstm_prefill(p["slstm"], layer.xlstm, h)
            else:
                y = xlstm_mod.slstm_full(p["slstm"], layer.xlstm, h)
        x = x + checkpoint_name(y, "block_out")
    aux = jnp.zeros((), jnp.float32)
    if layer.mlp in ("dense", "moe"):
        with jax.named_scope("norm"):
            h2 = apply_norm(p["norm2"], x, cfg.norm)
            h2 = logical_constraint(h2, cc.BATCH, cc.SEQ, cc.EMBED)
        with jax.named_scope("mlp"):
            if layer.mlp == "dense":
                y2 = mlp_mod.mlp(p["mlp"], h2, cfg.act)
            elif want_cache:          # serving: dropless, this layer's share
                y2, counts = mlp_mod.moe_dropless(p["moe"], layer.moe, h2,
                                                  cfg.act)
            else:
                y2, aux = mlp_mod.moe(p["moe"], layer.moe, h2, cfg.act,
                                      seq_chunk=cfg.moe_seq_chunk)
            x = x + checkpoint_name(y2, "block_out")
    x = logical_constraint(x, cc.BATCH, cc.SEQ, cc.EMBED)
    return x, aux, cache, counts


def layer_decode(p, layer: LayerSpec, cfg: ModelConfig, x, pos, cache,
                 index=None):
    """Single-token layer step. Returns (x, new_cache, counts).

    With ``index`` (int32 scalar), ``cache`` is the segment's layer-stacked
    cache and this layer is its slab ``index``; the returned cache stays
    stacked. Attention layers append their row in place; recurrent layers
    replace their whole state slab. An MoE layer is dropless and returns
    its counts; every other layer returns empty ``counts``."""
    counts = {}
    with jax.named_scope("norm"):
        h = apply_norm(p["norm1"], x, cfg.norm)
    with jax.named_scope("attn"):
        if layer.kind == "attn":
            y, cache = attn_mod.attn_decode(p["attn"], layer.attn, h, pos,
                                            cache, index)
        elif layer.kind == "mla":
            y, cache = attn_mod.mla_decode(p["mla"], layer.mla, h, pos, cache,
                                           layer=index)
        else:
            state = jax.tree.map(lambda a: attn_mod.cache_slab(a, index),
                                 cache)
            if layer.kind == "mamba":
                y, state = ssm_mod.mamba_decode(p["mamba"], layer.mamba, h,
                                                state)
            elif layer.kind == "mlstm":
                y, state = xlstm_mod.mlstm_decode(p["mlstm"], layer.xlstm, h,
                                                  state)
            elif layer.kind == "slstm":
                y, state = xlstm_mod.slstm_decode(p["slstm"], layer.xlstm, h,
                                                  state)
            cache = state if index is None else jax.tree.map(
                lambda a, s: attn_mod.cache_put(a, s, (0,) * s.ndim, index),
                cache, state)
        x = x + y
    if layer.mlp in ("dense", "moe"):
        with jax.named_scope("norm"):
            h2 = apply_norm(p["norm2"], x, cfg.norm)
        with jax.named_scope("mlp"):
            if layer.mlp == "dense":
                y2 = mlp_mod.mlp(p["mlp"], h2, cfg.act)
            else:
                y2, counts = mlp_mod.moe_dropless(p["moe"], layer.moe, h2,
                                                  cfg.act)
            x = x + y2
    return x, cache, counts


def block_full(block_p, seg: Segment, cfg: ModelConfig, x, positions,
               want_cache: bool, max_len: int):
    """One block (all layers of a segment repetition). Returns
    (x, aux_sum, [caches], counts)."""
    aux_sum = jnp.zeros((), jnp.float32)
    caches, counts = [], {}
    for p_i, layer in zip(block_p, seg.layers):
        x, aux, cache, c = layer_full(p_i, layer, cfg, x, positions,
                                      want_cache, max_len)
        aux_sum = aux_sum + aux
        caches.append(cache)
        counts = _add_counts(counts, c)
    return x, aux_sum, caches, counts


def block_decode(block_p, block_c, seg: Segment, cfg: ModelConfig, x, pos,
                 index=None):
    new_caches, counts = [], {}
    for p_i, c_i, layer in zip(block_p, block_c, seg.layers):
        x, c, n = layer_decode(p_i, layer, cfg, x, pos, c_i, index)
        new_caches.append(c)
        counts = _add_counts(counts, n)
    return x, new_caches, counts


# ---------------------------------------------------------------------------
# Whole model
# ---------------------------------------------------------------------------
def init_params(cfg: ModelConfig, key) -> PyTree:
    keys = jax.random.split(key, len(cfg.segments) + 3)
    dt = _dtype(cfg)
    params: dict = {
        "embed": truncnorm_init(keys[0], (cfg.vocab_size, cfg.d_model),
                                0.02, dt),
        "final_norm": _norm_params(cfg, cfg.d_model),
        "segments": [],
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = truncnorm_init(keys[1],
                                           (cfg.d_model, cfg.vocab_size),
                                           0.02, dt)
    for i, seg in enumerate(cfg.segments):
        seg_keys = jax.random.split(keys[2 + i], seg.count)
        if seg.count == 1:
            params["segments"].append(init_block(seg_keys[0], seg, cfg))
        else:
            params["segments"].append(
                jax.vmap(lambda k, _s=seg: init_block(k, _s, cfg))(seg_keys))
    return params


def init_caches(cfg: ModelConfig, batch: int, max_len: int) -> list:
    caches = []
    for seg in cfg.segments:
        block = [layer_cache_init(l, cfg, batch, max_len) for l in seg.layers]
        if seg.count == 1:
            caches.append(block)
        else:
            caches.append(jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (seg.count,) + x.shape),
                block))
    return caches


def _maybe_remat(fn, cfg: ModelConfig):
    if not cfg.remat:
        return fn
    # "outputs": save the attention/MLP block outputs (checkpoint_name'd
    # below) so the backward pass does not recompute them — trades a few GB
    # of seq-sharded bf16 saves for ~the forward's HBM traffic (SSPerf I4).
    policy = cc.RUNTIME.get("remat_policy", "") or "nothing"
    if policy == "outputs":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.save_only_these_names(
                "block_out"))
    return jax.checkpoint(fn,
                          policy=jax.checkpoint_policies.nothing_saveable)


def backbone_full(params, cfg: ModelConfig, x, positions,
                  want_cache: bool, max_len: int):
    """Run all segments over embeddings x. Returns (x, aux, caches,
    counts)."""
    aux_total = jnp.zeros((), jnp.float32)
    caches, counts = [], {}
    for seg, seg_p in zip(cfg.segments, params["segments"]):
        if seg.count == 1:
            fn = _maybe_remat(
                lambda p, h, _s=seg: block_full(p, _s, cfg, h, positions,
                                                want_cache, max_len), cfg)
            x, aux, cache, c = fn(seg_p, x)
            aux_total = aux_total + aux
            caches.append(cache)
        else:
            def body(carry, p_i, _seg=seg):
                h, aux_acc = carry
                h2, aux_i, cache_i, c_i = block_full(
                    p_i, _seg, cfg, h, positions, want_cache, max_len)
                return (h2, aux_acc + aux_i), (cache_i, c_i)

            body_fn = _maybe_remat(body, cfg)
            (x, aux_total), (seg_caches, c) = jax.lax.scan(
                body_fn, (x, aux_total), seg_p)
            caches.append(seg_caches)
            c = _sum_layers(c)
        counts = _add_counts(counts, c)
    return x, aux_total, caches, counts


def _logits(params, cfg: ModelConfig, x):
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = x @ head.astype(x.dtype)
    if cfg.logits_fp32:
        logits = logits.astype(jnp.float32)
    return logical_constraint(logits, cc.BATCH, None, cc.VOCAB)


def forward(params, cfg: ModelConfig, tokens=None, embeds=None,
            want_cache: bool = False, max_len: int = 0):
    """tokens: (B,S) int32 (or embeds (B,S,d)). Returns (logits, aux, caches)."""
    return _forward(params, cfg, tokens, embeds, want_cache, max_len)[:3]


def _forward(params, cfg: ModelConfig, tokens, embeds, want_cache: bool,
             max_len: int):
    """``forward``, and the layers' counts summed: (logits, aux, caches,
    counts)."""
    if embeds is None:
        with jax.named_scope("embed"):
            embeds = params["embed"][tokens]
    x = logical_constraint(embeds, cc.BATCH, cc.SEQ, cc.EMBED)
    b, s = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    max_len = max_len or s
    x, aux, caches, counts = backbone_full(params, cfg, x, positions,
                                           want_cache, max_len)
    with jax.named_scope("lm_head"):
        x = apply_norm(params["final_norm"], x, cfg.norm)
        return _logits(params, cfg, x), aux, caches, counts


def _chunked_ce(params, cfg: ModelConfig, x, labels):
    """Seq-chunked CE: logits for one seq chunk at a time (rematerialized),
    so the (B, S, V) fp32 logits never exist — the fix for huge-vocab
    training memory (gemma3's 262k vocab: 4.3 GB/device of logits at
    train_4k). Exact: CE decomposes over positions."""
    b, s, d = x.shape
    chunk = cfg.ce_chunk
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    nc = s // chunk
    xc = x.reshape(b, nc, chunk, d).transpose(1, 0, 2, 3)
    lc = labels.reshape(b, nc, chunk).transpose(1, 0, 2)

    def body(args):
        x_blk, l_blk = args
        logits = (x_blk @ head.astype(x_blk.dtype)).astype(jnp.float32)
        logits = logical_constraint(logits, cc.BATCH, None, cc.VOCAB)
        m = (l_blk >= 0).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, jnp.maximum(l_blk, 0)[..., None],
                                   axis=-1)[..., 0]
        return jnp.sum(nll * m), jnp.sum(m)

    nlls, counts = jax.lax.map(jax.checkpoint(body), (xc, lc))
    return jnp.sum(nlls) / jnp.maximum(jnp.sum(counts), 1.0)


def loss_and_metrics(params, cfg: ModelConfig, batch: dict):
    """batch: {"tokens": (B,S), "labels": (B,S)}; labels -100 = masked."""
    labels = batch["labels"]
    b, s = batch["tokens"].shape
    if cfg.ce_chunk and s % cfg.ce_chunk == 0 and s > cfg.ce_chunk:
        with jax.named_scope("embed"):
            embeds = params["embed"][batch["tokens"]]
        x = logical_constraint(embeds, cc.BATCH, cc.SEQ, cc.EMBED)
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None],
                                     (b, s))
        x, aux, _, _ = backbone_full(params, cfg, x, positions, False, s)
        with jax.named_scope("lm_head"):
            x = apply_norm(params["final_norm"], x, cfg.norm)
            ce = _chunked_ce(params, cfg, x, labels)
    else:
        logits, aux, _ = forward(params, cfg, tokens=batch["tokens"])
        mask = (labels >= 0).astype(jnp.float32)
        ce = cross_entropy(logits, jnp.maximum(labels, 0), mask)
    loss = ce + aux
    return loss, {"loss": loss, "ce": ce, "aux": aux}


def prefill(params, cfg: ModelConfig, tokens=None, embeds=None,
            max_len: int = 0, counts: bool = False):
    """Returns (logits_last (B,1,V), caches), and with ``counts`` also the
    MoE layers' counts summed over layers."""
    logits, _, caches, n = _forward(params, cfg, tokens, embeds, True,
                                    max_len)
    return (logits[:, -1:], caches) + ((n,) if counts else ())


def _default_layout(x):
    """``x`` held to the layout the default device gives an array of its
    shape: the layout the caches enter and leave the step in. On a TPU that
    is row-major for the slots-minor caches, the layout the decode kernel
    reads; without the constraint XLA may give the decode scan's carry
    another layout its body prefers and copy every stacked cache whole into
    and out of the loop."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return x
    pjrt = dev.client.get_default_layout(np.dtype(x.dtype), x.shape, dev)
    return with_layout_constraint(
        x, Layout(Layout.from_pjrt_layout(pjrt).major_to_minor))


def decode_step(params, cfg: ModelConfig, token, pos, caches,
                counts: bool = False):
    """token: (B,1) int32; pos: scalar int32. Returns (logits, new_caches),
    and with ``counts`` also the step's MoE counts summed over layers."""
    with jax.named_scope("embed"):
        x = params["embed"][token]
    new_caches, n = [], {}
    for seg, seg_p, seg_c in zip(cfg.segments, params["segments"], caches):
        if seg.count == 1:
            x, c, n_seg = block_decode(seg_p, seg_c, seg, cfg, x, pos)
            new_caches.append(c)
        else:
            def body(carry, xs, _seg=seg):
                h, c = carry
                p_i, idx = xs
                h, c, n_i = block_decode(p_i, c, _seg, cfg, h, pos, idx)
                return (h, jax.tree.map(_default_layout, c)), n_i

            (x, seg_new), n_seg = jax.lax.scan(
                body, (x, seg_c),
                (seg_p, jnp.arange(seg.count, dtype=jnp.int32)))
            new_caches.append(seg_new)
            n_seg = _sum_layers(n_seg)
        n = _add_counts(n, n_seg)
    with jax.named_scope("lm_head"):
        x = apply_norm(params["final_norm"], x, cfg.norm)
        logits = _logits(params, cfg, x)
    return (logits, new_caches) + ((n,) if counts else ())


def param_count(params) -> int:
    return sum(int(p.size) for p in jax.tree_util.tree_leaves(params))
