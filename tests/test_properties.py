"""Property-style invariant sweeps (seeded randomized — hypothesis is not
installed in this container, so the sweeps are explicit and deterministic).

System invariants under test:
  * Algorithm 1 assignments: disjoint groups, memory-feasible groups,
    deterministic, total (with repair) when capacity exists.
  * Disaster recovery: invariants survive arbitrary failure sets.
  * Sharding rules: divisibility always holds, whatever the shape.
  * Data pipeline: shards partition the global batch, replay-exact.
  * Checkpointing: bit-exact roundtrip across dtypes/shapes.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh

from repro.checkpoint import restore_pytree, save_pytree
from repro.core import assign as assign_mod
from repro.core import cost_model as cm
from repro.core import train as gnn_train
from repro.core.graph import random_fleet
from repro.data.synthetic import SyntheticConfig, make_batch
from repro.parallel.sharding import ShardingRules, _fit_axes

TASK_SETS = [
    [cm.GPT2_1_5B, cm.BERT_LARGE],
    [cm.T5_11B, cm.GPT2_1_5B, cm.ROBERTA],
]


@pytest.fixture(scope="module")
def gnn_small():
    tasks = TASK_SETS[0]
    cfg = gnn_train.gnn_config_for(tasks)
    ds = gnn_train.make_dataset(3, tasks, n_nodes=16, seed=3, label_frac=0.8)
    # joint default: ~3x the old sequential epoch count (one update/epoch)
    params, _ = gnn_train.train_gnn(cfg, ds, steps=50, lr=0.01)
    return tasks, params, cfg


def _check_invariants(graph, tasks, assignment):
    mem = graph.memory_gb()
    by_name = {t.name: t for t in tasks}
    all_ids = [i for ids in assignment.groups.values() for i in ids]
    assert len(all_ids) == len(set(all_ids)), "groups overlap"
    assert all(0 <= i < graph.n for i in all_ids), "id out of range"
    for name, ids in assignment.groups.items():
        assert sum(mem[i] for i in ids) >= by_name[name].min_memory_gb, \
            f"{name} group under its memory threshold"
    # every task either placed or deferred
    placed = set(assignment.groups) | set(assignment.deferred)
    assert {t.name for t in tasks} <= placed


@pytest.mark.parametrize("seed", range(6))
def test_assignment_invariants_random_fleets(gnn_small, seed):
    tasks, params, cfg = gnn_small
    fleet = random_fleet(10 + 3 * seed, seed=seed)
    a1 = assign_mod.task_assignments(fleet, tasks, params, cfg)
    a2 = assign_mod.task_assignments(fleet, tasks, params, cfg)
    _check_invariants(fleet, tasks, a1)
    assert a1.groups == a2.groups, "assignment must be deterministic"


@pytest.mark.parametrize("seed", range(4))
def test_recovery_invariants(gnn_small, seed):
    tasks, params, cfg = gnn_small
    fleet = random_fleet(14, seed=100 + seed)
    a = assign_mod.task_assignments(fleet, tasks, params, cfg)
    rng = np.random.default_rng(seed)
    failed = sorted(rng.choice(fleet.n, size=3, replace=False).tolist())
    survivors, a2 = assign_mod.recover(fleet, a, failed, tasks, params, cfg)
    assert survivors.n == fleet.n - 3
    _check_invariants(survivors, tasks, a2)


def test_capacity_error_raised(gnn_small):
    tasks, params, cfg = gnn_small
    tiny = random_fleet(2, seed=0)
    huge = [cm.OPT_175B, cm.OPT_175B, cm.OPT_175B, cm.OPT_175B,
            cm.OPT_175B, cm.OPT_175B]
    with pytest.raises(assign_mod.PlacementError):
        assign_mod.task_assignments(tiny, huge, params, cfg)


# ---------------------------------------------------------------------------
# Sharding
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("seed", range(8))
def test_fit_axes_always_divides(seed):
    rng = np.random.default_rng(seed)
    mesh = AbstractMesh((2, 16, 16), ("pod", "data", "model"))
    for _ in range(50):
        dim = int(rng.integers(1, 70000))
        axes = tuple(rng.permutation(["pod", "data", "model"]))
        fitted = _fit_axes(dim, axes, mesh, set())
        prod = int(np.prod([mesh.shape[a] for a in fitted])) if fitted else 1
        assert dim % prod == 0


# ---------------------------------------------------------------------------
# Data pipeline
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("num_shards", [1, 2, 4, 8])
def test_shards_partition_batch(num_shards):
    from repro.configs import get_config, reduce_for_smoke
    cfg = reduce_for_smoke(get_config("starcoder2-3b"))
    parts = [make_batch(cfg, SyntheticConfig(global_batch=16, seq_len=8,
                                             seed=1, shard_id=i,
                                             num_shards=num_shards), 3)
             for i in range(num_shards)]
    rows = np.concatenate([p["tokens"] for p in parts], axis=0)
    assert rows.shape == (16, 8)
    # distinct shards produce distinct rows (overwhelmingly likely)
    if num_shards > 1:
        assert not np.array_equal(parts[0]["tokens"], parts[1]["tokens"])


# ---------------------------------------------------------------------------
# Checkpoint roundtrip sweep
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32,
                                   jnp.float16])
@pytest.mark.parametrize("shape", [(), (3,), (2, 5), (2, 3, 4)])
def test_checkpoint_roundtrip_sweep(tmp_path, dtype, shape):
    key = jax.random.PRNGKey(hash((str(dtype), shape)) % 2**31)
    if jnp.issubdtype(dtype, jnp.integer):
        leaf = jax.random.randint(key, shape, -5, 100).astype(dtype)
    else:
        leaf = jax.random.normal(key, shape).astype(dtype)
    tree = {"x": leaf, "nested": [leaf, {"y": leaf}]}
    p = str(tmp_path / "ck")
    save_pytree(p, tree)
    back = restore_pytree(p, jax.tree.map(jnp.zeros_like, tree))
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        np.testing.assert_array_equal(
            np.atleast_1d(np.asarray(a)).view(np.uint8),
            np.atleast_1d(np.asarray(b)).view(np.uint8))
        assert a.dtype == b.dtype and a.shape == b.shape
