"""Chunked execution paths (the shardable dry-run forms) must match the
full/quadratic reference forms: attention q-chunking, MLA q-chunking,
chunkwise Mamba scan, chunkwise-recurrent mLSTM."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import AttnSpec, MLASpec, MambaSpec, XLSTMSpec
from repro.models import attention as attn_mod
from repro.models import ssm as ssm_mod
from repro.models import xlstm as xlstm_mod
from repro.models.common import Runtime, use_runtime


def _positions(b, s):
    return jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))


@pytest.mark.parametrize("window", [None, 24])
def test_chunked_attention_matches_full(window):
    b, s, d = 2, 128, 64
    spec = AttnSpec(n_heads=4, n_kv_heads=2, head_dim=16, window=window)
    p = attn_mod.init_attn(jax.random.PRNGKey(0), spec, d, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (b, s, d))
    y_full = attn_mod.attn_full(p, spec, x, _positions(b, s))
    with use_runtime(Runtime(seq_chunk=32)):
        y_chunk = attn_mod.attn_full(p, spec, x, _positions(b, s))
    np.testing.assert_allclose(np.asarray(y_chunk), np.asarray(y_full),
                               rtol=2e-5, atol=2e-5)


def test_chunked_attention_grad_matches():
    b, s, d = 1, 64, 32
    spec = AttnSpec(n_heads=4, n_kv_heads=4, head_dim=8)
    p = attn_mod.init_attn(jax.random.PRNGKey(2), spec, d, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(3), (b, s, d))

    def loss(p):
        return jnp.sum(attn_mod.attn_full(p, spec, x, _positions(b, s)) ** 2)

    g_full = jax.grad(loss)(p)
    with use_runtime(Runtime(seq_chunk=16)):
        g_chunk = jax.grad(loss)(p)
    for a, b_ in zip(jax.tree.leaves(g_full), jax.tree.leaves(g_chunk)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-4, atol=1e-5)


def test_chunked_mla_matches_full():
    b, s, d = 2, 96, 64
    spec = MLASpec(n_heads=4, q_lora_rank=16, kv_lora_rank=16, qk_nope_dim=8,
                   qk_rope_dim=8, v_head_dim=8)
    p = attn_mod.init_mla(jax.random.PRNGKey(4), spec, d, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(5), (b, s, d))
    y_full = attn_mod.mla_full(p, spec, x, _positions(b, s))
    with use_runtime(Runtime(seq_chunk=32)):
        y_chunk = attn_mod.mla_full(p, spec, x, _positions(b, s))
    np.testing.assert_allclose(np.asarray(y_chunk), np.asarray(y_full),
                               rtol=2e-5, atol=2e-5)


def test_chunkwise_mamba_matches_full():
    b, s, d = 2, 128, 32
    spec = MambaSpec(d_state=8)
    p = ssm_mod.init_mamba(jax.random.PRNGKey(6), spec, d, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(7), (b, s, d))
    y_full = ssm_mod.mamba_full(p, spec, x)
    _, cache_full = ssm_mod.mamba_prefill(p, spec, x)
    with use_runtime(Runtime(seq_chunk=16)):
        y_chunk = ssm_mod.mamba_full(p, spec, x)
        _, cache_chunk = ssm_mod.mamba_prefill(p, spec, x)
    np.testing.assert_allclose(np.asarray(y_chunk), np.asarray(y_full),
                               rtol=5e-5, atol=5e-5)
    np.testing.assert_allclose(np.asarray(cache_chunk["h"]),
                               np.asarray(cache_full["h"]),
                               rtol=5e-5, atol=5e-5)


def test_chunkwise_mlstm_matches_full():
    b, s, d = 2, 128, 32
    spec = XLSTMSpec(n_heads=2, proj_factor=2.0, conv_width=4)
    p = xlstm_mod.init_mlstm(jax.random.PRNGKey(8), spec, d, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(9), (b, s, d))
    y_full = xlstm_mod.mlstm_full(p, spec, x)
    _, cache_full = xlstm_mod.mlstm_prefill(p, spec, x)
    with use_runtime(Runtime(seq_chunk=16)):
        y_chunk = xlstm_mod.mlstm_full(p, spec, x)
        _, cache_chunk = xlstm_mod.mlstm_prefill(p, spec, x)
    np.testing.assert_allclose(np.asarray(y_chunk), np.asarray(y_full),
                               rtol=5e-5, atol=5e-5)
    for key in ("c", "n", "m"):
        np.testing.assert_allclose(np.asarray(cache_chunk[key]),
                                   np.asarray(cache_full[key]),
                                   rtol=5e-5, atol=5e-5, err_msg=key)


def test_chunkwise_mlstm_state_feeds_decode():
    """Chunkwise prefill state must continue correctly through decode."""
    b, s, d = 1, 64, 32
    spec = XLSTMSpec(n_heads=2, proj_factor=2.0, conv_width=4)
    p = xlstm_mod.init_mlstm(jax.random.PRNGKey(10), spec, d, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(11), (b, s + 1, d))
    with use_runtime(Runtime(seq_chunk=16)):
        _, cache = xlstm_mod.mlstm_prefill(p, spec, x[:, :s])
        y_dec, _ = xlstm_mod.mlstm_decode(p, spec, x[:, s:], cache)
    y_full = xlstm_mod.mlstm_full(p, spec, x)
    np.testing.assert_allclose(np.asarray(y_dec[:, 0]),
                               np.asarray(y_full[:, -1]),
                               rtol=1e-4, atol=1e-4)


def test_moe_chunked_matches_full():
    """Grouped/scanned MoE == single-group MoE when capacity never drops."""
    import dataclasses
    from repro.configs.base import MoESpec
    from repro.models import mlp as mlp_mod
    b, s, d = 2, 64, 16
    spec = MoESpec(n_experts=4, top_k=2, d_ff_expert=32,
                   capacity_factor=8.0)   # high cf: no token dropping
    p = mlp_mod.init_moe(jax.random.PRNGKey(12), spec, d, "silu", jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(13), (b, s, d))
    y_full, aux_full = mlp_mod.moe(p, spec, x, "silu", seq_chunk=0)
    y_chunk, aux_chunk = mlp_mod.moe(p, spec, x, "silu", seq_chunk=16)
    np.testing.assert_allclose(np.asarray(y_chunk), np.asarray(y_full),
                               rtol=2e-5, atol=2e-5)


def test_moe_capacity_drops_tokens():
    """With a tight capacity factor some tokens must be dropped (output
    differs from the no-drop run) but the result stays finite."""
    from repro.configs.base import MoESpec
    from repro.models import mlp as mlp_mod
    b, s, d = 2, 512, 16
    spec_tight = MoESpec(n_experts=4, top_k=2, d_ff_expert=32,
                         capacity_factor=0.5)
    p = mlp_mod.init_moe(jax.random.PRNGKey(14), spec_tight, d, "silu",
                         jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(15), (b, s, d))
    y, aux = mlp_mod.moe(p, spec_tight, x, "silu")
    assert np.isfinite(np.asarray(y)).all()
    assert np.isfinite(float(aux))


def test_chunked_ce_matches_full():
    """ce_chunk path == full-logits CE (exact decomposition)."""
    import dataclasses
    from repro.configs import get_config, reduce_for_smoke
    from repro.models import decoder_lm as dlm
    from repro.data.synthetic import SyntheticConfig, make_batch
    cfg0 = dataclasses.replace(reduce_for_smoke(get_config("gemma3-1b")),
                               remat=False, ce_chunk=0)
    cfg1 = dataclasses.replace(cfg0, ce_chunk=8)
    params = dlm.init_params(cfg0, jax.random.PRNGKey(0))
    batch = {k: jnp.asarray(v) for k, v in make_batch(
        cfg0, SyntheticConfig(global_batch=2, seq_len=32), 0).items()}
    loss0, m0 = dlm.loss_and_metrics(params, cfg0, batch)
    loss1, m1 = dlm.loss_and_metrics(params, cfg1, batch)
    np.testing.assert_allclose(float(loss1), float(loss0), rtol=1e-5)

    g0 = jax.grad(lambda p: dlm.loss_and_metrics(p, cfg0, batch)[0])(params)
    g1 = jax.grad(lambda p: dlm.loss_and_metrics(p, cfg1, batch)[0])(params)
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-4, atol=1e-6)
