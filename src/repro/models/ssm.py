"""Mamba (selective SSM) block — Jamba's recurrent layer.

Training/prefill uses ``jax.lax.associative_scan`` over the sequence (the
TPU-friendly parallel form of the selective scan; the GPU paper's fused CUDA
kernel maps to a log-depth scan + elementwise ops here). Decode keeps O(1)
state: (h: (B, d_inner, d_state), conv ring: (B, d_conv-1, d_inner)).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import MambaSpec
from repro.models import common as cc
from repro.models.common import dense_init, logical_constraint


def dt_rank(spec: MambaSpec, d_model: int) -> int:
    return spec.dt_rank or max(1, math.ceil(d_model / 16))


def d_inner(spec: MambaSpec, d_model: int) -> int:
    return spec.expand * d_model


def init_mamba(key, spec: MambaSpec, d_model: int, dtype) -> dict:
    ks = jax.random.split(key, 6)
    di = d_inner(spec, d_model)
    dr = dt_rank(spec, d_model)
    # S4D-real initialization for A
    a = jnp.tile(jnp.arange(1, spec.d_state + 1, dtype=jnp.float32)[None, :],
                 (di, 1))
    return {
        "in_proj": dense_init(ks[0], d_model, 2 * di, dtype),
        "conv_w": (jax.random.normal(ks[1], (spec.d_conv, di)) * 0.1).astype(dtype),
        "conv_b": jnp.zeros((di,), dtype),
        "x_proj": dense_init(ks[2], di, dr + 2 * spec.d_state, dtype),
        "dt_proj": dense_init(ks[3], dr, di, dtype),
        "dt_bias": jnp.log(jnp.expm1(
            jnp.exp(jax.random.uniform(ks[4], (di,), jnp.float32,
                                       jnp.log(1e-3), jnp.log(1e-1))))),
        "a_log": jnp.log(a),
        "d_skip": jnp.ones((di,), jnp.float32),
        "out_proj": dense_init(ks[5], di, d_model, dtype),
    }


def _ssm_params(p, spec: MambaSpec, u):
    """u: (B, S, di) -> discretized (dA (B,S,di,ds), dBu (B,S,di,ds), C)."""
    dr = p["dt_proj"].shape[0]
    xp = u @ p["x_proj"]                                     # (B,S,dr+2ds)
    dt_in, b_mat, c_mat = jnp.split(xp, [dr, dr + spec.d_state], axis=-1)
    dt = jax.nn.softplus((dt_in @ p["dt_proj"]).astype(jnp.float32)
                         + p["dt_bias"])                     # (B,S,di)
    a = -jnp.exp(p["a_log"])                                 # (di, ds)
    da = jnp.exp(dt[..., None] * a)                          # (B,S,di,ds)
    dbu = (dt * u.astype(jnp.float32))[..., None] \
        * b_mat.astype(jnp.float32)[..., None, :]            # (B,S,di,ds)
    return da, dbu, c_mat.astype(jnp.float32)


def _causal_conv(p, spec: MambaSpec, u):
    """Depthwise causal conv over seq. u: (B,S,di)."""
    pad = spec.d_conv - 1
    x = jnp.pad(u, ((0, 0), (pad, 0), (0, 0)))
    out = jax.lax.conv_general_dilated(
        x, p["conv_w"][:, None, :],                 # (K, 1, di)
        window_strides=(1,), padding="VALID",
        dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=u.shape[-1])
    return jax.nn.silu(out + p["conv_b"])


def _combine(e1, e2):
    a1, b1 = e1
    a2, b2 = e2
    return a1 * a2, a2 * b1 + b2


def _scan_ssm(p, spec: MambaSpec, u):
    """Selective scan over u (B,S,di) -> (y_ssm fp32 (B,S,di), h_last).

    With ``Runtime.seq_chunk`` set, runs chunkwise: the (B,C,di,ds)
    discretized tensors live one chunk at a time (lax.scan over chunks,
    rematerialized) instead of (B,S,di,ds) at once — this is what lets the
    4k/32k shapes lower within HBM. Chunked == full exactly (the recurrence
    composes associatively)."""
    b, s, di = u.shape
    chunk = cc.runtime().seq_chunk
    if not chunk or s <= chunk or s % chunk != 0:
        da, dbu, c_mat = _ssm_params(p, spec, u)
        hs = jax.lax.associative_scan(_combine, (da, dbu), axis=1)[1]
        y = jnp.einsum("bsdn,bsn->bsd", hs, c_mat)
        return y, hs[:, -1]

    n = s // chunk
    u_c = u.reshape(b, n, chunk, di).transpose(1, 0, 2, 3)   # (n,B,C,di)

    def body(h0, u_i):
        da, dbu, c_i = _ssm_params(p, spec, u_i)
        cum_a, hs0 = jax.lax.associative_scan(_combine, (da, dbu), axis=1)
        hs = hs0 + cum_a * h0[:, None]                       # carry in
        y_i = jnp.einsum("bsdn,bsn->bsd", hs, c_i)
        return hs[:, -1], y_i

    h_last, ys = jax.lax.scan(jax.checkpoint(body),
                              jnp.zeros((b, di, spec.d_state), jnp.float32),
                              u_c)
    return ys.transpose(1, 0, 2, 3).reshape(b, s, di), h_last


def mamba_full(p, spec: MambaSpec, x):
    """Train/prefill. x: (B,S,d) -> (B,S,d)."""
    b, s, d = x.shape
    ug = x @ p["in_proj"]
    u, gate = jnp.split(ug, 2, axis=-1)                      # (B,S,di) each
    u = logical_constraint(u, cc.BATCH, None, cc.FF)
    u = _causal_conv(p, spec, u)
    y, _ = _scan_ssm(p, spec, u)
    y = (y + p["d_skip"] * u.astype(jnp.float32)).astype(x.dtype)
    y = y * jax.nn.silu(gate)
    y = logical_constraint(y, cc.BATCH, None, cc.FF)
    return y @ p["out_proj"]


def mamba_prefill(p, spec: MambaSpec, x):
    """Forward + final recurrent state. x: (B,S,d) -> (y, cache)."""
    b, s, d = x.shape
    ug = x @ p["in_proj"]
    u_pre, gate = jnp.split(ug, 2, axis=-1)
    u = _causal_conv(p, spec, u_pre)
    y, h_last = _scan_ssm(p, spec, u)
    y = (y + p["d_skip"] * u.astype(jnp.float32)).astype(x.dtype)
    y = y * jax.nn.silu(gate)
    y = y @ p["out_proj"]
    # final state + conv tail (pre-conv activations feed the decode window)
    tail = spec.d_conv - 1
    conv_tail = u_pre[:, -tail:, :] if s >= tail else jnp.pad(
        u_pre, ((0, 0), (tail - s, 0), (0, 0)))
    cache = {"h": h_last, "conv": conv_tail}
    return y, cache


def init_mamba_cache(spec: MambaSpec, d_model: int, batch: int, dtype) -> dict:
    di = d_inner(spec, d_model)
    return {
        "h": jnp.zeros((batch, di, spec.d_state), jnp.float32),
        "conv": jnp.zeros((batch, spec.d_conv - 1, di), dtype),
    }


def mamba_decode(p, spec: MambaSpec, x, cache: dict):
    """One-token step. x: (B,1,d)."""
    b = x.shape[0]
    ug = x @ p["in_proj"]
    u, gate = jnp.split(ug, 2, axis=-1)                      # (B,1,di)
    window = jnp.concatenate([cache["conv"], u], axis=1)     # (B,K,di)
    conv_out = jnp.einsum("bkd,kd->bd", window.astype(jnp.float32),
                          p["conv_w"].astype(jnp.float32)) + p["conv_b"]
    u1 = jax.nn.silu(conv_out)[:, None, :].astype(x.dtype)   # (B,1,di)
    da, dbu, c_mat = _ssm_params(p, spec, u1)
    h = cache["h"] * da[:, 0] + dbu[:, 0]                    # (B,di,ds)
    y = jnp.einsum("bdn,bn->bd", h, c_mat[:, 0])
    y = (y + p["d_skip"] * u1[:, 0].astype(jnp.float32)).astype(x.dtype)
    y = (y[:, None, :] * jax.nn.silu(gate)) @ p["out_proj"]
    new_cache = {"h": h, "conv": window[:, 1:]}
    return y, new_cache
