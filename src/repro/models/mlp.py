"""MLP layers: dense (gated-SiLU or plain-GeLU) and Mixture-of-Experts.

Two MoE paths share one routing rule (``select_experts``: softmax scores,
plain or group-limited greedy top-k, optional renormalisation and scale):

  * ``moe`` — training and dry-runs: GShard-style capacity routing with
    one-hot dispatch/combine einsums, the formulation XLA SPMD partitions
    well (tokens sharded on the data axis, experts on the model axis; the
    dispatch einsum's contraction over tokens becomes the
    all-to-all/reduce-scatter). Long sequences are chunked through the MoE
    with lax.scan (cfg.moe_seq_chunk) to bound live dispatch tensors. Tokens
    over an expert's capacity are dropped.
  * ``moe_dropless`` — serving (prefill and decode): every (token, expert)
    pair whose expert this layer holds is computed, by one grouped matmul
    (``jax.lax.ragged_dot``) over the pairs sorted by expert. Shapes are
    static for the worst case, every pair held here.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import MoESpec
from repro.models import common as cc
from repro.models.common import activate, dense_init, logical_constraint


# ---------------------------------------------------------------------------
# Dense MLP
# ---------------------------------------------------------------------------
def init_mlp(key, d_model: int, d_ff: int, act: str, dtype) -> dict:
    ks = jax.random.split(key, 3)
    p = {
        "w_up": dense_init(ks[0], d_model, d_ff, dtype),
        "w_down": dense_init(ks[1], d_ff, d_model, dtype),
    }
    if act == "silu":  # gated (SwiGLU)
        p["w_gate"] = dense_init(ks[2], d_model, d_ff, dtype)
    return p


def mlp(p, x, act: str):
    h = x @ p["w_up"]
    if "w_gate" in p:
        h = activate(x @ p["w_gate"], act) * h
    else:
        h = activate(h, act)
    h = logical_constraint(h, cc.BATCH, None, cc.FF)
    return h @ p["w_down"]


# ---------------------------------------------------------------------------
# Mixture of Experts
# ---------------------------------------------------------------------------
def init_moe(key, spec: MoESpec, d_model: int, act: str, dtype) -> dict:
    ks = jax.random.split(key, 5)
    e, f = spec.held, spec.d_ff_expert
    p = {
        "router": dense_init(ks[0], d_model, spec.n_experts, jnp.float32,
                             scale=0.01),
        "w_up": jax.random.truncated_normal(
            ks[1], -2, 2, (e, d_model, f)).astype(dtype) * (d_model ** -0.5),
        "w_down": jax.random.truncated_normal(
            ks[2], -2, 2, (e, f, d_model)).astype(dtype) * (f ** -0.5),
    }
    if act == "silu":
        p["w_gate"] = jax.random.truncated_normal(
            ks[3], -2, 2, (e, d_model, f)).astype(dtype) * (d_model ** -0.5)
    if spec.n_shared:
        p["shared"] = init_mlp(ks[4], d_model, f * spec.n_shared, act, dtype)
    return p


def _expert_ffn(p, x_gecd, act: str):
    """x: (G, E, C, d) -> (G, E, C, d), batched over groups x experts."""
    h = jnp.einsum("gecd,edf->gecf", x_gecd, p["w_up"])
    if "w_gate" in p:
        g = jnp.einsum("gecd,edf->gecf", x_gecd, p["w_gate"])
        h = activate(g, act) * h
    else:
        h = activate(h, act)
    h = logical_constraint(h, cc.BATCH, cc.EXPERT, None, None)
    return jnp.einsum("gecf,efd->gecd", h, p["w_down"])


def select_experts(router_w, x, spec: MoESpec):
    """The routing rule. x (..., d) -> (probs (..., E), weights (..., k)
    float32, experts (..., k) int32).

    Softmax scores over all ``n_experts``. With ``n_group`` > 1, greedy
    group-limited: each group is scored by its best expert, the token keeps
    its ``topk_group`` best groups and takes its top-k experts among them.
    Weights are the experts' probabilities, renormalised to sum 1 if
    ``norm_topk``, times ``routed_scale``."""
    e = spec.n_experts
    logits = jnp.einsum("...d,de->...e", x.astype(jnp.float32), router_w)
    probs = jax.nn.softmax(logits, axis=-1)
    scores = probs
    if spec.n_group > 1:
        grouped = probs.reshape(probs.shape[:-1] + (spec.n_group, -1))
        _, top_groups = jax.lax.top_k(grouped.max(-1), spec.topk_group)
        kept = jnp.sum(jax.nn.one_hot(top_groups, spec.n_group,
                                      dtype=jnp.int32), axis=-2) > 0
        scores = jnp.where(jnp.repeat(kept, e // spec.n_group, axis=-1),
                           probs, 0.0)
    weights, experts = jax.lax.top_k(scores, spec.top_k)
    if spec.norm_topk:
        weights = weights / jnp.maximum(
            jnp.sum(weights, axis=-1, keepdims=True), 1e-9)
    if spec.routed_scale != 1.0:
        weights = weights * spec.routed_scale
    return probs, weights, experts


def _route(router_w, x, spec: MoESpec, capacity: int):
    """GShard grouped top-k capacity routing. x: (G, n, d) — every group
    routes independently with per-group capacity, so the dispatch tensor is
    (G, n, E, C) with C ~ n·k/E (linear in total tokens, not quadratic).
    Returns (dispatch, combine (G,n,E,C), aux_loss)."""
    g_, n, _ = x.shape
    e = spec.n_experts
    probs, gate_vals, gate_idx = select_experts(router_w, x, spec)

    # aux load-balance loss (Switch): E * sum_e f_e * P_e, averaged over groups
    me = jnp.mean(probs, axis=1)                             # (G, E)
    ce = jnp.zeros((g_, e), jnp.float32)
    for k in range(spec.top_k):
        ce = ce + jnp.mean(jax.nn.one_hot(gate_idx[:, :, k], e,
                                          dtype=jnp.float32), axis=1)
    aux = e * jnp.mean(jnp.sum(me * ce / spec.top_k, axis=-1))

    # bf16 routing tensors halve the dominant (G,n,E,C) HBM traffic; gate
    # weights are in [0,1] so bf16's 0.4% relative error is routing-benign
    # (SSPerf deepseek I6; default stays f32 — knob for the perf runs).
    rdt = jnp.bfloat16 if cc.RUNTIME.get("moe_combine_bf16") else jnp.float32
    combine = jnp.zeros((g_, n, e, capacity), rdt)
    prev_counts = jnp.zeros((g_, e), jnp.int32)
    for k in range(spec.top_k):
        mask_k = jax.nn.one_hot(gate_idx[:, :, k], e, dtype=jnp.int32)
        pos_k = jnp.cumsum(mask_k, axis=1) - 1 + prev_counts[:, None, :]
        prev_counts = prev_counts + jnp.sum(mask_k, axis=1)
        keep = (pos_k < capacity) & (mask_k > 0)
        # keep the per-k routing tensors expert-sharded (the (G,n,E,C)
        # one-hots dominate MoE HBM traffic when replicated over `model`)
        pos_oh = jax.nn.one_hot(pos_k, capacity, dtype=rdt)
        pos_oh = logical_constraint(pos_oh, cc.BATCH, None, cc.EXPERT, None)
        combine = combine + (gate_vals[:, :, k, None, None].astype(rdt)
                             * keep[..., None] * pos_oh)
        combine = logical_constraint(combine, cc.BATCH, None, cc.EXPERT,
                                     None)
    dispatch = (combine > 0)
    return dispatch, combine, aux


def _moe_grouped(p, spec: MoESpec, x_gnd, act: str, capacity: int):
    """x: (G, n, d) -> (y (G, n, d), aux)."""
    dispatch, combine, aux = _route(p["router"], x_gnd, spec, capacity)
    dispatched = jnp.einsum("gnec,gnd->gecd", dispatch.astype(x_gnd.dtype),
                            x_gnd)
    dispatched = logical_constraint(dispatched, cc.BATCH, cc.EXPERT, None,
                                    None)
    out = _expert_ffn(p, dispatched, act)
    y = jnp.einsum("gnec,gecd->gnd", combine.astype(x_gnd.dtype), out)
    return y, aux


def moe(p, spec: MoESpec, x, act: str, seq_chunk: int = 0):
    """x: (B, S, d) -> (y, aux_loss): the capacity path, for training and
    dry-runs (serving takes ``moe_dropless``).

    Scalable path (seq_chunk set): groups = batch rows,
    lax.scan over seq chunks with a rematerialized body — per-step live
    dispatch is (B, chunk, E, C) with per-group capacity C = chunk·k/E·cf.
    The batch dim keeps the data sharding; experts ride the model axis, so
    the dispatch einsum's token contraction becomes the expected
    reduce-scatter/all-to-all under SPMD."""
    b, s, d = x.shape
    n = b * s
    if spec.held != spec.n_experts:
        raise ValueError("the capacity path holds every expert; a share of "
                         "them is served by moe_dropless")
    # launcher/perf-iteration overrides (0 = use the config's values)
    seq_chunk = cc.RUNTIME.get("moe_chunk", 0) or seq_chunk
    cf = cc.RUNTIME.get("moe_capacity_factor", 0.0) or spec.capacity_factor

    if seq_chunk and s % seq_chunk == 0 and s > seq_chunk:
        n_chunks = s // seq_chunk
        cap = max(1, int(seq_chunk * spec.top_k / spec.n_experts * cf))
        xc = x.reshape(b, n_chunks, seq_chunk, d).transpose(1, 0, 2, 3)

        def body(carry, xi):                       # xi (B, chunk, d)
            yi, aux_i = _moe_grouped(p, spec, xi, act, cap)
            return carry + aux_i, yi

        aux_sum, yc = jax.lax.scan(jax.checkpoint(body),
                                   jnp.zeros((), jnp.float32), xc)
        y = yc.transpose(1, 0, 2, 3).reshape(b * s, d)
        aux = aux_sum / n_chunks
    else:
        if n <= 256:
            capacity = n                   # no dropping on tiny token counts
        else:
            capacity = max(1, int(n * spec.top_k / spec.n_experts
                                  * spec.capacity_factor))
        y, aux = _moe_grouped(p, spec, x.reshape(1, n, d), act, capacity)
        y = y.reshape(n, d)

    x_flat = x.reshape(n, d)
    if spec.n_shared:
        y = y + mlp(p["shared"], x_flat, act)
    return y.reshape(b, s, d), aux * spec.router_aux_weight


# ---------------------------------------------------------------------------
# Dropless MoE over this layer's share of the experts (serving)
# ---------------------------------------------------------------------------
def moe_dropless(p, spec: MoESpec, x, act: str):
    """x: (B, S, d) -> (y, counts).

    The router scores all ``n_experts``; of the top-k pairs only those whose
    expert is held here (``first_local`` .. + ``held``) are computed. Pairs
    are sorted by local expert, the held ones first, and the three expert
    matmuls run as grouped matmuls over the sorted rows; rows past the held
    pairs belong to no group and are masked out. Each token's weighted
    results are summed in float32, then the shared experts are added once.
    ``counts`` holds two int32 scalars: ``moe_pairs``, the held pairs, and
    ``moe_expert_visits``, the held experts with at least one."""
    b, s, d = x.shape
    n, k, e_held = b * s, spec.top_k, spec.held
    xf = x.reshape(n, d)
    with jax.named_scope("moe_route"):
        _, weights, experts = select_experts(p["router"], xf, spec)
        local = experts - spec.first_local                      # (n, k)
        held = (local >= 0) & (local < e_held)
        key = jnp.where(held, local, e_held).reshape(-1)        # (n*k,)
        order = jnp.argsort(key, stable=True)
        sizes = jnp.sum(jax.nn.one_hot(key, e_held, dtype=jnp.int32), axis=0)
        xs = xf[order // k]                                     # (n*k, d)
    with jax.named_scope("moe_experts"):
        h = jax.lax.ragged_dot(xs, p["w_up"], sizes)
        if "w_gate" in p:
            h = activate(jax.lax.ragged_dot(xs, p["w_gate"], sizes), act) * h
        else:
            h = activate(h, act)
        ys = jax.lax.ragged_dot(h, p["w_down"], sizes)          # (n*k, d)
    with jax.named_scope("moe_combine"):
        inverse = jnp.zeros_like(order).at[order].set(
            jnp.arange(n * k, dtype=order.dtype))
        ys = ys[inverse].reshape(n, k, d).astype(jnp.float32)
        y = jnp.sum(jnp.where(held[..., None], ys * weights[..., None], 0.0),
                    axis=1).astype(x.dtype)
    if spec.n_shared:
        with jax.named_scope("shared_experts"):
            y = y + mlp(p["shared"], xf, act)
    counts = {"moe_pairs": jnp.sum(held, dtype=jnp.int32),
              "moe_expert_visits": jnp.sum(sizes > 0, dtype=jnp.int32)}
    return y.reshape(b, s, d), counts
