"""ShapeDtypeStruct stand-ins + sharding specs for every lowered entry point.

No device allocation anywhere: param/cache structures come from
``jax.eval_shape`` over the real initializers, so the dry-run lowers exactly
what the runtime would execute.

Cache sharding policy (decode shapes):
  * batch dim        -> (pod, data)   [dropped when indivisible, e.g. B=1]
  * KV-cache seq dim, the last (caches hold their slots minor) -> (model,
    data) minus already-used axes — sharding the cache T dim turns the
    decode softmax/dot into partial+all-reduce (a flash-decode schedule via
    GSPMD); with B=1 (long_500k) the cache spreads over the whole pod.
  * mamba/xlstm state feature dims -> model.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeSpec
from repro.data.synthetic import batch_struct
from repro.models import decoder_lm as dlm
from repro.models.registry import get_api
from repro.parallel.sharding import ShardingRules, _fit_axes, param_specs
from repro.training.optimizer import AdamWConfig
from repro.training.train_step import TrainState, init_train_state

PyTree = Any


def _act_dtype(cfg: ModelConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


def input_specs(cfg: ModelConfig, shape: ShapeSpec) -> dict:
    """ShapeDtypeStruct batch for train/prefill lowering."""
    skel = batch_struct(cfg, shape.global_batch, shape.seq_len,
                        _act_dtype(cfg))
    return {k: jax.ShapeDtypeStruct(s, d) for k, (s, d) in skel.items()}


def param_struct(cfg: ModelConfig) -> PyTree:
    api = get_api(cfg)
    return jax.eval_shape(lambda k: api.init_params(cfg, k),
                          jax.random.PRNGKey(0))


def train_state_struct(cfg: ModelConfig, opt_cfg: AdamWConfig) -> PyTree:
    return jax.eval_shape(
        lambda k: init_train_state(cfg, k, opt_cfg=opt_cfg),
        jax.random.PRNGKey(0))


# ---------------------------------------------------------------------------
# Sharding spec trees
# ---------------------------------------------------------------------------
def _ns(rules: ShardingRules, spec_tree: PyTree) -> PyTree:
    return jax.tree.map(lambda s: NamedSharding(rules.mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def train_state_specs(rules: ShardingRules, state_struct: PyTree) -> PyTree:
    pspecs = param_specs(rules, state_struct.params)
    mspecs_mu = param_specs(rules, state_struct.opt.mu)
    mspecs_nu = param_specs(rules, state_struct.opt.nu)
    from repro.training.optimizer import AdamState
    return TrainState(params=pspecs,
                      opt=AdamState(step=P(), mu=mspecs_mu, nu=mspecs_nu))


def batch_partition_specs(rules: ShardingRules, batch: dict) -> dict:
    out = {}
    for k, v in batch.items():
        fitted = _fit_axes(v.shape[0], ("pod",) + tuple(rules.data_axes),
                           rules.mesh, set())
        spec = [None] * len(v.shape)
        if fitted:
            spec[0] = fitted if len(fitted) > 1 else fitted[0]
        out[k] = P(*spec)
    return out


# -- caches -------------------------------------------------------------------
_BATCH_AXES = ("pod", "data")
_SEQ_AXES = ("model", "data")


def _cache_leaf_spec(kind: str, name: str, shape: tuple, stacked: bool,
                     rules: ShardingRules) -> P:
    mesh = rules.mesh
    off = 1 if stacked else 0
    spec: list = [None] * len(shape)
    used: set = set()

    def put(i, axes):
        fitted = _fit_axes(shape[i], tuple(a for a in axes if a not in used),
                           mesh, used)
        if fitted:
            spec[i] = fitted if len(fitted) > 1 else fitted[0]
            used.update(fitted)

    core_rank = len(shape) - off
    if name == "slot_pos":
        return P(*spec)
    if kind in ("attn", "mla") and name in ("k", "v", "latent") \
            and core_rank == 4:        # (B, KV, D, T): slots minor
        put(off + 0, _BATCH_AXES)
        put(off + 3, _SEQ_AXES)        # flash-decode style cache split
    elif kind == "mamba":
        put(off + 0, _BATCH_AXES)
        if name == "h" and core_rank == 3:
            put(off + 1, ("model",))
        elif name == "conv" and core_rank == 3:
            put(off + 2, ("model",))
    elif kind in ("mlstm", "slstm"):
        put(off + 0, _BATCH_AXES)
        if name == "conv" and core_rank == 3:
            put(off + 2, ("model",))
        elif core_rank >= 2:
            put(off + 1, ("model",))   # heads (usually dropped: few heads)
    elif name == "cross_kv" and core_rank == 4:   # (B, T_enc, KV, dh)
        put(off + 0, _BATCH_AXES)
        put(off + 2, ("model",))
    else:                               # generic fallback
        put(off + 0, _BATCH_AXES)
    return P(*spec)


def decode_cache_struct(cfg: ModelConfig, batch: int, max_len: int) -> PyTree:
    if cfg.family == "audio":
        api = get_api(cfg)
        params_s = param_struct(cfg)
        frames = jax.ShapeDtypeStruct((batch, cfg.encoder_max_len,
                                       cfg.d_model), _act_dtype(cfg))
        tokens = jax.ShapeDtypeStruct((batch, 1), jnp.int32)
        _, caches = jax.eval_shape(
            lambda p, f, t: api.prefill(p, cfg, f, t, max_len=max_len),
            params_s, frames, tokens)
        return caches
    return jax.eval_shape(lambda: dlm.init_caches(cfg, batch, max_len))


def decode_cache_specs(rules: ShardingRules, cfg: ModelConfig, batch: int,
                       max_len: int) -> PyTree:
    """PartitionSpec tree mirroring decode_cache_struct — built by walking
    cfg.segments exactly as init_caches does (no rank heuristics)."""

    def block_specs(seg, stacked: bool):
        out = []
        for layer in seg.layers:
            c = jax.eval_shape(
                lambda l=layer: dlm.layer_cache_init(l, cfg, batch, max_len))
            spec = {k: _cache_leaf_spec(layer.kind, k, ((0,) if stacked else ())
                                        + tuple(v.shape), stacked, rules)
                    for k, v in c.items()}
            out.append(spec)
        return out

    self_specs = [block_specs(seg, seg.count > 1) for seg in cfg.segments]
    if cfg.family != "audio":
        return self_specs

    def cross_specs(seg, stacked):
        out = []
        for layer in seg.layers:
            kv_shape = (batch, cfg.encoder_max_len, layer.attn.n_kv_heads,
                        layer.attn.head_dim)
            s = _cache_leaf_spec("attn", "cross_kv",
                                 ((0,) if stacked else ()) + kv_shape,
                                 stacked, rules)
            out.append((s, s))
        return out

    return {"self": self_specs,
            "cross": [cross_specs(seg, seg.count > 1)
                      for seg in cfg.segments]}


def token_specs(rules: ShardingRules, batch: int):
    fitted = _fit_axes(batch, _BATCH_AXES, rules.mesh, set())
    spec = [None, None]
    if fitted:
        spec[0] = fitted if len(fitted) > 1 else fitted[0]
    return P(*spec)
