"""Per-architecture smoke tests: REDUCED config of the same family, one
forward/train step on CPU, asserting output shapes + no NaNs; plus
prefill -> decode-step consistency passes (one step, and several chained
steps) for decoder-bearing archs, and a check that decode keeps the
layer-stacked caches in the layer scan's carry.

The FULL configs are exercised only via the dry-run (ShapeDtypeStruct)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config, reduce_for_smoke
from repro.data.synthetic import SyntheticConfig, make_batch
from repro.models.registry import get_api
from repro.training.optimizer import AdamWConfig, adamw_init, adamw_update

B, S = 2, 16


def _smoke_cfg(arch):
    import dataclasses
    cfg = reduce_for_smoke(get_config(arch))
    return dataclasses.replace(cfg, remat=False)  # faster smoke compile


def _batch(cfg):
    return {k: jnp.asarray(v) for k, v in make_batch(
        cfg, SyntheticConfig(global_batch=B, seq_len=S, seed=0), 0).items()}


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step(arch):
    cfg = _smoke_cfg(arch)
    api = get_api(cfg)
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg)

    def loss_fn(p):
        loss, metrics = api.loss_and_metrics(p, cfg, batch)
        return loss, metrics

    (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
    assert np.isfinite(float(loss)), f"{arch}: non-finite loss"
    # one optimizer step must stay finite
    opt_cfg = AdamWConfig(learning_rate=1e-3)
    state = adamw_init(params)
    new_params, state, om = adamw_update(opt_cfg, grads, state, params)
    leaves = jax.tree_util.tree_leaves(new_params)
    assert all(np.isfinite(np.asarray(l, np.float32)).all() for l in leaves), \
        f"{arch}: non-finite params after update"
    assert float(om["grad_norm"]) > 0.0, f"{arch}: zero gradient"


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_shapes(arch):
    cfg = _smoke_cfg(arch)
    api = get_api(cfg)
    params = api.init_params(cfg, jax.random.PRNGKey(1))
    batch = _batch(cfg)
    loss, metrics = api.loss_and_metrics(params, cfg, batch)
    assert loss.shape == ()
    assert np.isfinite(float(metrics["ce"]))


def _prefill_and_forward(arch, seed, n_prompt, max_len):
    """Full-forward logits at the text positions (B, S, V), the caches after
    prefilling tokens[:, :n_prompt], the position of text token 0, and the
    tokens."""
    cfg = _smoke_cfg(arch)
    api = get_api(cfg)
    params = api.init_params(cfg, jax.random.PRNGKey(seed))
    batch = _batch(cfg)
    tokens = batch["tokens"]
    if cfg.family == "audio":
        from repro.models import encdec
        enc_out = encdec.encode(params, cfg, batch["frames"])
        full_logits, _ = encdec._decoder(params, cfg, tokens, enc_out)
        _, caches = api.prefill(params, cfg, batch["frames"],
                                tokens[:, :n_prompt], max_len=max_len)
        return cfg, api, params, full_logits, caches, 0, tokens
    from repro.models import decoder_lm as dlm
    if cfg.family == "vlm":
        from repro.models import vlm as vlm_mod
        embeds = vlm_mod._embed_multimodal(params, cfg, batch["patches"],
                                           tokens)
        full_logits, _, _ = dlm.forward(params, cfg, embeds=embeds)
        p = batch["patches"].shape[1]       # positions include the patches
        _, caches = api.prefill(params, cfg, batch["patches"],
                                tokens[:, :n_prompt], max_len=p + max_len)
        return cfg, api, params, full_logits[:, p:], caches, p, tokens
    full_logits, _, _ = dlm.forward(params, cfg, tokens=tokens)
    _, caches = api.prefill(params, cfg, tokens=tokens[:, :n_prompt],
                            max_len=max_len)
    return cfg, api, params, full_logits, caches, 0, tokens


def _check_decode_steps(arch, seed, steps):
    """Prefill tokens[:, :S-steps], then run ``steps`` chained decode_steps,
    each fed the caches the previous one returned; each step's logits must
    match the full forward's at its position (teacher forcing)."""
    n_prompt = S - steps
    cfg, api, params, full_logits, caches, off, tokens = \
        _prefill_and_forward(arch, seed, n_prompt, S + 4)
    for t in range(n_prompt, S):
        step_logits, caches = api.decode_step(params, cfg, tokens[:, t:t + 1],
                                              jnp.int32(off + t), caches)
        np.testing.assert_allclose(np.asarray(step_logits, np.float32),
                                   np.asarray(full_logits[:, t:t + 1],
                                              np.float32),
                                   rtol=2e-3, atol=2e-3,
                                   err_msg=f"{arch}: decode at {t}")
        assert np.isfinite(np.asarray(step_logits)).all()


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_decode_consistency(arch):
    """decode_step(t) after prefill(0..t-1) must match the full forward's
    logits at position t (teacher forcing)."""
    _check_decode_steps(arch, 2, 1)


@pytest.mark.parametrize("arch", ARCHS)
def test_multi_step_decode_consistency(arch):
    """Four chained decode_steps after prefill(0..S-5): a row written at the
    wrong layer or slot shows in a later step even where one step misses it.
    The smoke ring caches (window 8) wrap during these steps."""
    _check_decode_steps(arch, 3, 4)


@pytest.mark.parametrize("arch", ["phi3-mini-3.8b", "starcoder2-3b"])
def test_decode_scan_carries_caches(arch):
    """decode_step scans a count > 1 segment with its stacked caches in the
    scan's carry only: no cache leaf is among the scan's xs, and no ys has a
    stacked cache's shape (the xs/ys form copies every layer's cache out and
    back in each step)."""
    from repro.models import decoder_lm as dlm
    cfg = _smoke_cfg(arch)
    assert any(seg.count > 1 for seg in cfg.segments)
    api = get_api(cfg)
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    caches = dlm.init_caches(cfg, B, S)
    token = jnp.zeros((B, 1), jnp.int32)
    closed = jax.make_jaxpr(
        lambda c: api.decode_step(params, cfg, token, jnp.int32(3), c))(caches)
    cache_vars = closed.jaxpr.invars
    cache_shapes = {v.aval.shape for v in cache_vars}
    scans = [e for e in closed.jaxpr.eqns if e.primitive.name == "scan"]
    assert len(scans) == sum(seg.count > 1 for seg in cfg.segments)
    carried = []
    for eqn in scans:
        n_consts = eqn.params["num_consts"]
        n_carry = eqn.params["num_carry"]
        carry = eqn.invars[n_consts:n_consts + n_carry]
        xs = eqn.invars[n_consts + n_carry:]
        ys = eqn.outvars[n_carry:]
        assert not any(v in xs for v in cache_vars), f"{arch}: cache in xs"
        assert not any(v.aval.shape in cache_shapes for v in ys), \
            f"{arch}: cache-shaped ys"
        carried += [v for v in cache_vars if v in carry]
    assert len(carried) == len(cache_vars), f"{arch}: cache not carried"
