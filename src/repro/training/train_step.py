"""train_step / serve_step builders — the functions the launcher pjits and
the dry-run lowers.

All builders return *pure* functions over (state, batch) pytrees so they can
be jax.jit'ed with in_shardings/out_shardings derived from
parallel.sharding. TrainState = (params, opt_state, step).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.registry import ModelApi, get_api
from repro.training.optimizer import (AdamState, AdamWConfig, adamw_init,
                                      adamw_update)

PyTree = Any


class TrainState(NamedTuple):
    params: PyTree
    opt: AdamState


def init_train_state(cfg: ModelConfig, key,
                     opt_cfg: AdamWConfig | None = None) -> TrainState:
    api = get_api(cfg)
    params = api.init_params(cfg, key)
    moment_dtype = opt_cfg.moment_dtype if opt_cfg else "float32"
    return TrainState(params=params, opt=adamw_init(params, moment_dtype))


def make_train_step(cfg: ModelConfig, opt_cfg: AdamWConfig,
                    api: ModelApi | None = None) -> Callable:
    """(state, batch) -> (state, metrics)."""
    api = api or get_api(cfg)

    def train_step(state: TrainState, batch: dict):
        def loss_fn(p):
            loss, metrics = api.loss_and_metrics(p, cfg, batch)
            return loss, metrics

        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state.params)
        params, opt, om = adamw_update(opt_cfg, grads, state.opt, state.params)
        metrics = dict(metrics)
        metrics.update(om)
        return TrainState(params=params, opt=opt), metrics

    return train_step


def make_eval_step(cfg: ModelConfig, api: ModelApi | None = None) -> Callable:
    api = api or get_api(cfg)

    def eval_step(params, batch):
        _, metrics = api.loss_and_metrics(params, cfg, batch)
        return metrics

    return eval_step


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------
def make_prefill(cfg: ModelConfig, api: ModelApi | None = None,
                 counts: bool = False) -> Callable:
    """(params, batch, max_len) -> (last_logits, caches), and with
    ``counts`` (a decoder LM's) the MoE counts of the prompt as a third
    output (``decoder_lm.prefill``)."""
    api = api or get_api(cfg)

    def prefill_step(params, batch, max_len: int):
        if cfg.family == "audio":
            return api.prefill(params, cfg, batch["frames"], batch["tokens"],
                               max_len=max_len)
        if cfg.family == "vlm":
            return api.prefill(params, cfg, batch["patches"], batch["tokens"],
                               max_len=max_len)
        if counts:
            return api.prefill(params, cfg, tokens=batch["tokens"],
                               max_len=max_len, counts=True)
        return api.prefill(params, cfg, tokens=batch["tokens"],
                           max_len=max_len)

    return prefill_step


def make_decode_step(cfg: ModelConfig, api: ModelApi | None = None,
                     greedy: bool = True) -> Callable:
    """(params, token (B,1), pos scalar, caches) -> (next_token, new_caches).

    This is the `serve_step` the decode_* / long_* shapes lower: one new
    token against a KV cache of the shape's seq_len. Given a decoder LM's
    running MoE counts as a fifth argument (a dict, as ``make_prefill``'s
    ``counts`` returns them), it adds this step's and returns them as a
    third output."""
    api = api or get_api(cfg)

    def serve_step(params, token, pos, caches, *counts):
        if counts:
            logits, new_caches, step = api.decode_step(
                params, cfg, token, pos, caches, counts=True)
            counts = (jax.tree.map(jnp.add, counts[0], step),)
        else:
            logits, new_caches = api.decode_step(params, cfg, token, pos,
                                                 caches)
        with jax.named_scope("sample"):
            next_token = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return (next_token[:, None], new_caches) + counts

    return serve_step


def init_serve_caches(cfg: ModelConfig, batch: int, max_len: int):
    """Decode caches for serve_step (lm/vlm families; audio builds its own
    via prefill because of the cross-attention KV)."""
    from repro.models import decoder_lm as dlm
    return dlm.init_caches(cfg, batch, max_len)
