"""Sharding-rule tests: divisibility-awareness over real arch param shapes
(ShapeDtypeStruct trees — no allocation), using AbstractMesh so the 16x16
production mesh needs no real devices."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import ARCHS, get_config, reduce_for_smoke
from repro.models.registry import get_api
from repro.parallel.sharding import (DEFAULT_ACT_RULES, ShardingRules,
                                     _fit_axes, param_specs)

MESH = AbstractMesh((16, 16), ("data", "model"))
MESH3 = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def _param_structs(arch):
    cfg = get_config(arch)
    api = get_api(cfg)
    return cfg, jax.eval_shape(lambda k: api.init_params(cfg, k),
                               jax.random.PRNGKey(0))


def _check_divisibility(tree, specs, mesh):
    def ok(leaf, spec):
        for dim, ax in zip(leaf.shape, spec):
            if ax is None:
                continue
            axes = (ax,) if isinstance(ax, str) else ax
            size = int(np.prod([mesh.shape[a] for a in axes]))
            assert dim % size == 0, (leaf.shape, spec)

    jax.tree.map(ok, tree, specs,
                 is_leaf=lambda x: isinstance(x, P))


@pytest.mark.parametrize("arch", ["gemma3-1b", "qwen3-32b", "olmoe-1b-7b",
                                  "deepseek-v2-236b", "jamba-1.5-large-398b"])
def test_param_specs_divisible(arch):
    cfg, structs = _param_structs(arch)
    rules = ShardingRules(mesh=MESH)
    specs = param_specs(rules, structs)
    _check_divisibility(structs, specs, MESH)


def test_param_specs_multipod_divisible():
    cfg, structs = _param_structs("qwen3-32b")
    rules = ShardingRules(mesh=MESH3)
    specs = param_specs(rules, structs)
    _check_divisibility(structs, specs, MESH3)


def test_gemma3_heads_drop_tp():
    """gemma3 has 4 heads — model=16 TP cannot shard wq's output
    (4 heads x 256 = 1024 dim IS divisible by 16 though: rule applies to the
    fused dim). The guarantee under test is divisibility, not head count."""
    cfg, structs = _param_structs("gemma3-1b")
    rules = ShardingRules(mesh=MESH)
    specs = param_specs(rules, structs)
    _check_divisibility(structs, specs, MESH)


def test_fit_axes_drops_nondivisible():
    assert _fit_axes(4, ("model",), MESH, set()) == ()          # 4 % 16 != 0
    assert _fit_axes(64, ("model",), MESH, set()) == ("model",)
    assert _fit_axes(32, ("pod", "data"), MESH3, set()) == ("pod", "data")
    assert _fit_axes(2, ("pod", "data"), MESH3, set()) == ("pod",)
    assert _fit_axes(1, ("pod", "data"), MESH3, set()) == ()


def test_moe_experts_on_model_axis():
    cfg, structs = _param_structs("olmoe-1b-7b")
    rules = ShardingRules(mesh=MESH)
    specs = param_specs(rules, structs)
    # find a stacked moe w_up leaf: (count, E, D, F) -> spec (None, model, ...)
    flat = jax.tree_util.tree_flatten_with_path(
        specs, is_leaf=lambda x: isinstance(x, P))[0]
    moe_specs = [(p, s) for p, s in flat
                 if "moe" in "/".join(str(getattr(q, "key", "")) for q in p)
                 and "w_up" in str(p[-1])]
    assert moe_specs, "no moe leaves found"
    for path, spec in moe_specs:
        assert "model" in jax.tree.leaves(tuple(spec)), spec


def test_norms_replicated():
    cfg, structs = _param_structs("qwen3-32b")
    specs = param_specs(ShardingRules(mesh=MESH), structs)
    flat = jax.tree_util.tree_flatten_with_path(
        specs, is_leaf=lambda x: isinstance(x, P))[0]
    for path, spec in flat:
        names = "/".join(str(getattr(q, "key", "")) for q in path)
        if "norm" in names:
            assert all(s is None for s in spec), (names, spec)
