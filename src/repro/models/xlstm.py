"""xLSTM blocks: mLSTM (matrix memory, parallel-form training) and sLSTM
(scalar memory, sequential recurrence) — arXiv:2405.04517.

mLSTM training uses the stabilized quadratic parallel form (decay matrix D
from cumulative log-forget-gates); decode keeps O(1) state
(C: (B,H,dk,dv), n: (B,H,dk), m: (B,H)) — this is what makes long_500k decode
viable for this architecture. sLSTM has a true hidden-to-hidden recurrence, so
training runs a lax.scan over the sequence (block-diagonal per-head R).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import XLSTMSpec
from repro.models import common as cc
from repro.models.common import dense_init


# ---------------------------------------------------------------------------
# mLSTM
# ---------------------------------------------------------------------------
def _dims(spec: XLSTMSpec, d_model: int):
    d_in = int(spec.proj_factor * d_model)
    dh = d_in // spec.n_heads
    return d_in, dh


def init_mlstm(key, spec: XLSTMSpec, d_model: int, dtype) -> dict:
    ks = jax.random.split(key, 8)
    d_in, dh = _dims(spec, d_model)
    return {
        "up": dense_init(ks[0], d_model, 2 * d_in, dtype),
        "conv_w": (jax.random.normal(ks[1], (spec.conv_width, d_in)) * 0.1
                   ).astype(dtype),
        "conv_b": jnp.zeros((d_in,), dtype),
        "wq": dense_init(ks[2], d_in, d_in, dtype),
        "wk": dense_init(ks[3], d_in, d_in, dtype),
        "wv": dense_init(ks[4], d_in, d_in, dtype),
        "w_if": dense_init(ks[5], d_in, 2 * spec.n_heads, jnp.float32),
        "b_i": jnp.zeros((spec.n_heads,), jnp.float32),
        "b_f": jnp.full((spec.n_heads,), 3.0, jnp.float32),  # open forget gates
        "ogate_skip": dense_init(ks[6], d_model, d_in, dtype),
        "down": dense_init(ks[7], d_in, d_model, dtype),
    }


def _causal_conv(w, b, u):
    pad = w.shape[0] - 1
    x = jnp.pad(u, ((0, 0), (pad, 0), (0, 0)))
    out = jax.lax.conv_general_dilated(
        x, w[:, None, :], window_strides=(1,), padding="VALID",
        dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=u.shape[-1])
    return jax.nn.silu(out + b)


def _qkv_gates(p, spec: XLSTMSpec, x_main, conv_out):
    b, s, d_in = x_main.shape
    nh = spec.n_heads
    dh = d_in // nh
    q = (conv_out @ p["wq"]).reshape(b, s, nh, dh)
    k = (conv_out @ p["wk"]).reshape(b, s, nh, dh) * dh ** -0.5
    v = (x_main @ p["wv"]).reshape(b, s, nh, dh)
    gates = (x_main.astype(jnp.float32) @ p["w_if"]).reshape(b, s, nh, 2)
    i_pre = gates[..., 0] + p["b_i"]
    f_pre = gates[..., 1] + p["b_f"]
    return q, k, v, i_pre, f_pre


def _mlstm_quadratic(q, k, v, i_pre, f_pre):
    """Stabilized quadratic parallel form over one (sub)sequence with no
    incoming state. Returns h (B,S,H,dh) fp32."""
    b, s = q.shape[:2]
    logf = jax.nn.log_sigmoid(f_pre)                         # (B,S,H)
    cum = jnp.cumsum(logf, axis=1)                           # F_t
    # D~[t, s] = (F_t - F_s) + i~_s  for s <= t
    dmat = (cum[:, :, None, :] - cum[:, None, :, :]
            + i_pre[:, None, :, :])                          # (B,T,S,H)
    tril = jnp.tril(jnp.ones((s, s), bool))
    dmat = jnp.where(tril[None, :, :, None], dmat, -jnp.inf)
    m = jnp.max(dmat, axis=2, keepdims=True)                 # (B,T,1,H)
    m = jnp.maximum(m, -1e30)                                # rows can be all -inf only off-diag
    dexp = jnp.exp(dmat - m)                                 # (B,T,S,H)

    scores = jnp.einsum("bthd,bshd->btsh", q.astype(jnp.float32),
                        k.astype(jnp.float32))
    sd = scores * dexp
    norm = jnp.maximum(jnp.abs(jnp.sum(sd, axis=2, keepdims=True)),
                       jnp.exp(-m))                          # (B,T,1,H)
    w = sd / norm
    return jnp.einsum("btsh,bshd->bthd", w, v.astype(jnp.float32))


def _mlstm_chunkwise(q, k, v, i_pre, f_pre, chunk: int, state=None):
    """Chunkwise-recurrent mLSTM — the TPU-native form of the paper's fused
    recurrence (DESIGN.md SS3): the quadratic D matrix lives one
    (chunk x chunk) tile at a time; chunks compose through the O(1)
    (C, n, m) state exactly (same stabilization as the decode step, so
    chunked == full up to float associativity).

    Returns (h (B,S,H,dh) fp32, final state dict)."""
    b, s, nh, dh = q.shape
    n = s // chunk

    def to_chunks(t):
        return t.reshape(b, n, chunk, *t.shape[2:]).transpose(
            1, 0, 2, *range(3, t.ndim + 1))

    qc, kc, vc = to_chunks(q), to_chunks(k), to_chunks(v)
    ic, fc = to_chunks(i_pre), to_chunks(f_pre)
    if state is None:
        state = {
            "c": jnp.zeros((b, nh, dh, dh), jnp.float32),
            "n": jnp.zeros((b, nh, dh), jnp.float32),
            "m": jnp.full((b, nh), -1e30, jnp.float32),
        }

    def body(st, blk):
        q_, k_, v_, i_, f_ = blk                             # (B,L,H,*)
        c0, n0, m0 = st["c"], st["n"], st["m"]
        logf = jax.nn.log_sigmoid(f_)                        # (B,L,H)
        F = jnp.cumsum(logf, axis=1)
        dmat = (F[:, :, None, :] - F[:, None, :, :]
                + i_[:, None, :, :])                         # (B,L,L,H)
        tril = jnp.tril(jnp.ones((chunk, chunk), bool))
        dmat = jnp.where(tril[None, :, :, None], dmat, -jnp.inf)
        m_intra = jnp.maximum(jnp.max(dmat, axis=2), -1e30)  # (B,L,H)
        m_inter = F + m0[:, None]                            # (B,L,H)
        m_t = jnp.maximum(m_intra, m_inter)
        dexp = jnp.exp(dmat - m_t[:, :, None, :])
        qf = q_.astype(jnp.float32)
        scores = jnp.einsum("bthd,bshd->btsh", qf, k_.astype(jnp.float32))
        sd = scores * dexp
        inter_w = jnp.exp(m_inter - m_t)                     # (B,L,H)
        num = (jnp.einsum("btsh,bshd->bthd", sd, v_.astype(jnp.float32))
               + inter_w[..., None]
               * jnp.einsum("bthk,bhkv->bthv", qf, c0))
        den = (jnp.sum(sd, axis=2)
               + inter_w * jnp.einsum("bthk,bhk->bth", qf, n0))
        h = num / jnp.maximum(jnp.abs(den), jnp.exp(-m_t))[..., None]
        # state across the chunk boundary
        FL = F[:, -1]                                        # (B,H)
        wlog = (FL[:, None, :] - F) + i_                     # (B,L,H)
        m_new = jnp.maximum(FL + m0, jnp.max(wlog, axis=1))
        carry = jnp.exp(FL + m0 - m_new)                     # (B,H)
        wexp = jnp.exp(wlog - m_new[:, None, :])
        c_new = (carry[..., None, None] * c0
                 + jnp.einsum("bsh,bshk,bshv->bhkv", wexp,
                              k_.astype(jnp.float32), v_.astype(jnp.float32)))
        n_new = carry[..., None] * n0 + jnp.einsum(
            "bsh,bshk->bhk", wexp, k_.astype(jnp.float32))
        return {"c": c_new, "n": n_new, "m": m_new}, h

    st, hs = jax.lax.scan(jax.checkpoint(body), state, (qc, kc, vc, ic, fc))
    h = hs.transpose(1, 0, 2, 3, 4).reshape(b, s, nh, dh)
    return h, st


def _mlstm_inner(p, spec: XLSTMSpec, x, want_state: bool):
    b, s, d = x.shape
    up = x @ p["up"]
    x_main, z = jnp.split(up, 2, axis=-1)
    conv_out = _causal_conv(p["conv_w"], p["conv_b"], x_main)
    q, k, v, i_pre, f_pre = _qkv_gates(p, spec, x_main, conv_out)
    chunk = cc.runtime().seq_chunk
    state = None
    if chunk and s > chunk and s % chunk == 0:
        h, state = _mlstm_chunkwise(q, k, v, i_pre, f_pre, chunk)
    else:
        h = _mlstm_quadratic(q, k, v, i_pre, f_pre)
        if want_state:
            logf = jax.nn.log_sigmoid(f_pre)
            cum = jnp.cumsum(logf, axis=1)
            wlog = (cum[:, -1:, :] - cum) + i_pre            # (B,S,H)
            m = jnp.max(wlog, axis=1)                        # (B,H)
            wexp = jnp.exp(wlog - m[:, None, :])
            c = jnp.einsum("bsh,bshk,bshv->bhkv", wexp,
                           k.astype(jnp.float32), v.astype(jnp.float32))
            nst = jnp.einsum("bsh,bshk->bhk", wexp, k.astype(jnp.float32))
            state = {"c": c, "n": nst, "m": m}
    h = h.reshape(b, s, -1).astype(x.dtype)
    h = h * jax.nn.silu(z + x @ p["ogate_skip"])
    y = h @ p["down"]
    if not want_state:
        return y, None
    tail = spec.conv_width - 1
    conv_tail = x_main[:, -tail:, :] if s >= tail else jnp.pad(
        x_main, ((0, 0), (tail - s, 0), (0, 0)))
    cache = dict(state)
    cache["conv"] = conv_tail
    return y, cache


def mlstm_full(p, spec: XLSTMSpec, x):
    """Parallel stabilized form (chunkwise with ``Runtime.seq_chunk``).
    x: (B,S,d)."""
    y, _ = _mlstm_inner(p, spec, x, want_state=False)
    return y


def mlstm_prefill(p, spec: XLSTMSpec, x):
    """Forward + closed-form final state."""
    return _mlstm_inner(p, spec, x, want_state=True)


def init_mlstm_cache(spec: XLSTMSpec, d_model: int, batch: int, dtype) -> dict:
    d_in, dh = _dims(spec, d_model)
    nh = spec.n_heads
    return {
        "c": jnp.zeros((batch, nh, dh, dh), jnp.float32),
        "n": jnp.zeros((batch, nh, dh), jnp.float32),
        "m": jnp.full((batch, nh), -1e30, jnp.float32),
        "conv": jnp.zeros((batch, spec.conv_width - 1, d_in), dtype),
    }


def mlstm_decode(p, spec: XLSTMSpec, x, cache: dict):
    """O(1) recurrent step. x: (B,1,d)."""
    b = x.shape[0]
    up = x @ p["up"]
    x_main, z = jnp.split(up, 2, axis=-1)                    # (B,1,d_in)
    window = jnp.concatenate([cache["conv"], x_main], axis=1)
    conv_out = jax.nn.silu(
        jnp.einsum("bkd,kd->bd", window.astype(jnp.float32),
                   p["conv_w"].astype(jnp.float32)) + p["conv_b"])
    conv_out = conv_out[:, None, :].astype(x.dtype)
    q, k, v, i_pre, f_pre = _qkv_gates(p, spec, x_main, conv_out)
    q, k, v = q[:, 0], k[:, 0], v[:, 0]                      # (B,H,dh)
    i_pre, f_pre = i_pre[:, 0], f_pre[:, 0]                  # (B,H)

    logf = jax.nn.log_sigmoid(f_pre)
    m_new = jnp.maximum(logf + cache["m"], i_pre)
    f_eff = jnp.exp(logf + cache["m"] - m_new)[..., None]
    i_eff = jnp.exp(i_pre - m_new)[..., None]
    c = cache["c"] * f_eff[..., None] + i_eff[..., None] \
        * k[..., :, None] * v[..., None, :]                  # (B,H,dk,dv)
    n = cache["n"] * f_eff + i_eff * k
    denom = jnp.maximum(jnp.abs(jnp.einsum("bhk,bhk->bh", n,
                                           q.astype(jnp.float32))),
                        jnp.exp(-m_new))[..., None]
    h = jnp.einsum("bhkv,bhk->bhv", c, q.astype(jnp.float32)) / denom
    h = h.reshape(b, 1, -1).astype(x.dtype)
    h = h * jax.nn.silu(z + x @ p["ogate_skip"])
    y = h @ p["down"]
    new_cache = {"c": c, "n": n, "m": m_new, "conv": window[:, 1:]}
    return y, new_cache


# ---------------------------------------------------------------------------
# sLSTM
# ---------------------------------------------------------------------------
def init_slstm(key, spec: XLSTMSpec, d_model: int, dtype) -> dict:
    ks = jax.random.split(key, 4)
    nh = spec.n_heads
    dh = d_model // nh
    d_ff = int(d_model * 4 / 3)
    return {
        "w_gates": dense_init(ks[0], d_model, 4 * d_model, jnp.float32),
        # block-diagonal recurrent weights: per head (dh, 4*dh)
        "r_gates": (jax.random.normal(ks[1], (nh, dh, 4 * dh)) / dh ** 0.5
                    ).astype(jnp.float32),
        "b_gates": jnp.concatenate([
            jnp.zeros((d_model,)), jnp.full((d_model,), 3.0),   # i, f
            jnp.zeros((2 * d_model,))]).astype(jnp.float32),    # z, o
        "ffn_gate": dense_init(ks[2], d_model, d_ff, dtype),
        "ffn_up": dense_init(ks[2], d_model, d_ff, dtype),
        "ffn_down": dense_init(ks[3], d_ff, d_model, dtype),
    }


def init_slstm_state(spec: XLSTMSpec, d_model: int, batch: int) -> dict:
    nh = spec.n_heads
    dh = d_model // nh
    z = jnp.zeros((batch, nh, dh), jnp.float32)
    return {"c": z, "n": z + 1e-6, "h": z, "m": jnp.full((batch, nh, dh),
                                                         -1e30, jnp.float32)}


def _slstm_step(p, spec: XLSTMSpec, state, x_t):
    """x_t: (B, d_model) fp32. Returns (new_state, h_out (B, d_model))."""
    b, d = x_t.shape
    nh = spec.n_heads
    dh = d // nh
    h_prev = state["h"]                                      # (B,H,dh)
    rec = jnp.einsum("bhd,hde->bhe", h_prev, p["r_gates"])   # (B,H,4dh)
    gates = (x_t @ p["w_gates"] + p["b_gates"]).reshape(b, nh, 4, dh) \
        + rec.reshape(b, nh, 4, dh)
    i_pre, f_pre, z_pre, o_pre = (gates[:, :, 0], gates[:, :, 1],
                                  gates[:, :, 2], gates[:, :, 3])
    logf = jax.nn.log_sigmoid(f_pre)
    m_new = jnp.maximum(logf + state["m"], i_pre)
    i_eff = jnp.exp(i_pre - m_new)
    f_eff = jnp.exp(logf + state["m"] - m_new)
    z = jnp.tanh(z_pre)
    o = jax.nn.sigmoid(o_pre)
    c = f_eff * state["c"] + i_eff * z
    n = f_eff * state["n"] + i_eff
    h = o * c / jnp.maximum(n, 1e-6)
    new_state = {"c": c, "n": n, "h": h, "m": m_new}
    return new_state, h.reshape(b, d)


def slstm_full(p, spec: XLSTMSpec, x):
    """Sequential scan over seq (true recurrence). x: (B,S,d)."""
    b, s, d = x.shape
    state0 = init_slstm_state(spec, d, b)

    def body(state, x_t):
        return _slstm_step(p, spec, state, x_t)

    _, hs = jax.lax.scan(body, state0, x.astype(jnp.float32).swapaxes(0, 1))
    h = hs.swapaxes(0, 1).astype(x.dtype)                    # (B,S,d)
    # gated FFN (pf 4/3) as in the paper's sLSTM block
    f = jax.nn.gelu(h @ p["ffn_gate"]) * (h @ p["ffn_up"])
    return f @ p["ffn_down"]


def slstm_prefill(p, spec: XLSTMSpec, x):
    """Forward + final recurrent state."""
    b, s, d = x.shape
    state0 = init_slstm_state(spec, d, b)

    def body(state, x_t):
        return _slstm_step(p, spec, state, x_t)

    state, hs = jax.lax.scan(body, state0, x.astype(jnp.float32).swapaxes(0, 1))
    h = hs.swapaxes(0, 1).astype(x.dtype)
    f = jax.nn.gelu(h @ p["ffn_gate"]) * (h @ p["ffn_up"])
    return f @ p["ffn_down"], state


def slstm_decode(p, spec: XLSTMSpec, x, cache: dict):
    """x: (B,1,d)."""
    b, _, d = x.shape
    new_state, h = _slstm_step(p, spec, cache, x[:, 0].astype(jnp.float32))
    h = h[:, None, :].astype(x.dtype)
    f = jax.nn.gelu(h @ p["ffn_gate"]) * (h @ p["ffn_up"])
    return f @ p["ffn_down"], new_state
