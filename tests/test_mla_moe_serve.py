"""DeepSeek-V2's serve path at a small size on the CPU: MLA with YaRN and a
share of group-limited, dropless routed experts, against the plain float32
reference of the chip benchmark (``chipbench/references/mla_moe_decoder.py``)
on seeded random weights.

Sizes: d 64, 4 MLA heads, 16 experts in 4 groups, top-4 of 2 groups per
token, 2 shared experts, 1 dense and 2 MoE layers; this share holds experts
4..7 (group 1)."""
import dataclasses
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from chipbench.drivers import serve_moe as drv  # noqa: E402
from chipbench.references import mla_moe_decoder as ref  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.configs.base import MoESpec  # noqa: E402
from repro.launch.serve import serve_batch  # noqa: E402
from repro.models import attention as attn_mod  # noqa: E402
from repro.models import common as cc  # noqa: E402
from repro.models import decoder_lm as dlm  # noqa: E402
from repro.models import mlp as mlp_mod  # noqa: E402
from repro.models.common import Runtime, use_runtime  # noqa: E402

SMALL = dict(
    name="deepseek-v2-small", hidden_size=64, num_hidden_layers=3,
    first_k_dense_replace=1, num_attention_heads=4, q_lora_rank=32,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    intermediate_size=128, moe_intermediate_size=32, router_experts=16,
    n_routed_experts=4, first_local_expert=4, n_group=4, topk_group=2,
    num_experts_per_tok=4, n_shared_experts=2, routed_scaling_factor=16.0,
    norm_topk_prob=False, vocab_size=256, rms_norm_eps=1e-6,
    rope_theta=10000.0, served_dtype="float32",
    rope_scaling={"type": "yarn", "factor": 40, "mscale": 0.707,
                  "mscale_all_dim": 0.707,
                  "original_max_position_embeddings": 4096,
                  "beta_fast": 32, "beta_slow": 1})
B, PROMPT, GEN = 2, 24, 9          # prefill's token, then 8 decode steps


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _moe_spec(**kw) -> MoESpec:
    base = dict(n_experts=16, top_k=4, d_ff_expert=32, n_shared=2,
                n_group=4, topk_group=2, routed_scale=16.0, norm_topk=False)
    base.update(kw)
    return MoESpec(**base)


@pytest.mark.parametrize("kernels", [False, True], ids=["jnp", "interpret"])
def test_prefill_then_decode_matches_reference(kernels):
    """serve_batch's prefill and 8 decode steps serve the reference's best
    token at every position, the program's own logits along the served
    tokens match the reference's full forward, and the MoE pairs it counts
    are those the reference routes to the held experts; with ``kernels``
    prefill goes through the flash kernel and absorbed decode through the
    decode kernel, interpreted."""
    rt = Runtime(kernels=kernels)
    cfg = drv.model_config(SMALL)
    spec = ref.Spec.from_config(SMALL)
    key = jax.random.PRNGKey(7)
    params = _f32(drv.program_params(spec, key))
    prompt = np.asarray(jax.random.randint(jax.random.PRNGKey(8), (B, PROMPT),
                                           0, spec.vocab), np.int32)
    gen, stats = serve_batch(cfg, params, {"tokens": jnp.asarray(prompt)}, GEN,
                             log=lambda *a: None, runtime=rt)
    tokens = np.concatenate([prompt, gen[:, :-1]], axis=1)
    with jax.default_matmul_precision("highest"):
        want, held = ref.forward(spec, key, tokens, PROMPT - 1)   # (B, GEN, V)
    np.testing.assert_array_equal(gen, want.argmax(-1))

    with use_runtime(rt):
        logits, caches = dlm.prefill(params, cfg, tokens=jnp.asarray(prompt),
                                     max_len=PROMPT + GEN)
        got = [logits[:, -1]]
        for i in range(GEN - 1):
            logits, caches = dlm.decode_step(
                params, cfg, jnp.asarray(gen[:, i:i + 1]),
                jnp.int32(PROMPT + i), caches)
            got.append(logits[:, -1])
    np.testing.assert_allclose(np.stack(got, 1), want, rtol=2e-3, atol=2e-3)

    assert stats["moe_pairs_prefill"] == held[:, :PROMPT].sum() > 0
    assert stats["moe_pairs_decode"] == held[:, PROMPT:].sum() > 0
    layers = spec.layers - spec.first_dense
    assert (0 < stats["moe_expert_visits_decode"]
            <= min(stats["moe_pairs_decode"], (GEN - 1) * 4 * layers))


def test_group_limited_routing_matches_a_per_token_loop():
    spec = _moe_spec()
    router = jax.random.normal(jax.random.PRNGKey(1), (64, 16)) * 64 ** -0.5
    x = jax.random.normal(jax.random.PRNGKey(2), (50, 64))
    _, weights, experts = mlp_mod.select_experts(router, x, spec)
    logits = np.asarray(x, np.float64) @ np.asarray(router, np.float64)
    for t in range(x.shape[0]):
        p = np.exp(logits[t] - logits[t].max())
        p /= p.sum()
        best = p.reshape(4, 4).max(1)
        groups = np.argsort(-best)[:2]
        cand = [e for e in range(16) if e // 4 in groups]
        top = sorted(cand, key=lambda e: -p[e])[:4]
        assert sorted(np.asarray(experts[t]).tolist()) == sorted(top)
        np.testing.assert_allclose(np.sort(np.asarray(weights[t])),
                                   np.sort(16.0 * p[top]), rtol=1e-5)


def _expert_part(p, spec, x):
    """Per token, the held experts' weighted outputs, one token at a time."""
    _, w, e = mlp_mod.select_experts(p["router"], x, spec)
    out = np.zeros(x.shape, np.float32)
    for t in range(x.shape[0]):
        for wk, ek in zip(np.asarray(w[t]), np.asarray(e[t])):
            j = int(ek) - spec.first_local
            if 0 <= j < spec.held:
                h = (jax.nn.silu(x[t] @ p["w_gate"][j]) * (x[t] @ p["w_up"][j]))
                out[t] += wk * np.asarray(h @ p["w_down"][j])
    return out


def test_dropless_drops_no_token_when_every_token_routes_to_one_expert():
    """Every one of 512 tokens takes expert 5 (held): the dropless layer
    computes all 512 of its pairs, where the capacity path keeps 160."""
    spec = _moe_spec(n_shared=0, first_local=4, n_local=4)
    p = mlp_mod.init_moe(jax.random.PRNGKey(3), spec, 16, "silu", jnp.float32)
    p["router"] = p["router"].at[:, 5].set(1.0)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(4), (1, 512, 16)))
    y, counts = mlp_mod.moe_dropless(p, spec, x, "silu")
    _, _, e = mlp_mod.select_experts(p["router"], x[0], spec)
    assert bool(jnp.all(jnp.any(e == 5, axis=-1)))
    np.testing.assert_allclose(np.asarray(y[0]), _expert_part(p, spec, x[0]),
                               rtol=1e-4, atol=1e-5)
    assert int(counts["moe_pairs"]) == int(jnp.sum((e >= 4) & (e < 8)))
    full = dataclasses.replace(spec, n_local=0, first_local=0)
    p_full = dict(p, **{k: jnp.zeros((16,) + p[k].shape[1:]).at[4:8].set(p[k])
                        for k in ("w_up", "w_gate", "w_down")})
    y_cap, _ = mlp_mod.moe(p_full, full, x, "silu")
    assert not np.allclose(np.asarray(y_cap), np.asarray(y), atol=1e-4)


def test_expert_shares_add_up_to_the_uncut_layer():
    """model-configs section 4: the routed parts of the 4 shares of 4
    experts, with the shared experts counted once, equal the layer that
    holds all 16."""
    full = _moe_spec()
    p = mlp_mod.init_moe(jax.random.PRNGKey(5), full, 32, "silu", jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 12, 32))
    want, _ = mlp_mod.moe_dropless(p, full, x, "silu")
    routed = dict(p)
    shared = routed.pop("shared")
    total = mlp_mod.mlp(shared, x.reshape(-1, 32), "silu").reshape(x.shape)
    for first in range(0, 16, 4):
        share = dataclasses.replace(full, n_shared=0, first_local=first,
                                    n_local=4)
        part = dict(routed, **{k: routed[k][first:first + 4]
                               for k in ("w_up", "w_gate", "w_down")})
        y, _ = mlp_mod.moe_dropless(part, share, x, "silu")
        total = total + y
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_yarn_at_published_values():
    spec = get_config("deepseek-v2-236b").segments[1].layers[0].mla
    m = attn_mod.yarn_mscale(spec.yarn_factor, spec.yarn_mscale_all_dim)
    assert m == pytest.approx(0.1 * 0.707 * math.log(40) + 1)
    assert m == pytest.approx(1.2608, abs=1e-4)
    assert attn_mod.mla_softmax_scale(spec) == pytest.approx(192 ** -0.5 * m * m)
    assert attn_mod.yarn_ramp(spec) == (10, 23)
    freqs = np.asarray(attn_mod.mla_rope_freqs(spec))
    theta = 1.0 / 10000.0 ** (np.arange(32) / 32)
    np.testing.assert_allclose(freqs[:11], theta[:11], rtol=1e-6)
    np.testing.assert_allclose(freqs[23:], theta[23:] / 40, rtol=1e-6)
    assert np.all((freqs[11:23] < theta[11:23])
                  & (freqs[11:23] > theta[11:23] / 40))
    s = ref.Spec.from_config(dict(SMALL, qk_rope_head_dim=64))
    np.testing.assert_allclose(freqs, ref.yarn_inv_freq(s), rtol=1e-6)
    # mscale == mscale_all_dim: cos and sin are not scaled
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 5, 2, 64))
    pos = jnp.arange(5)[None]
    np.testing.assert_allclose(
        np.asarray(attn_mod._mla_rope(spec, x, pos)),
        np.asarray(cc.apply_rope(x, pos, 1e4, jnp.asarray(freqs))), rtol=1e-6)
