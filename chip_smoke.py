"""Run the system's main path once on a TPU chip and check what comes out.

    python chip_smoke.py             # one chip: planner, serve, train
    python chip_smoke.py --chips 4   # FSDP train step vs the same on one chip

One process, three phases, nothing caught:

1. planner: the GNN trained at its published widths (``GNNConfig()``) in the
   default joint mode, Algorithm 1 on the paper's 46-node fleet and on a
   generated 512-node fleet, and the Pallas ``scaled_spmm`` predict checked
   against the jnp predict on the same params;
2. serve: ``phi3-mini-3.8b`` at its published config through
   ``launch.serve.serve_batch``, and the Pallas flash/decode logits checked
   against the XLA attention path fed the same tokens;
3. train: the same widths cut to 4 layers through ``launch.train.train_loop``.

Weights, fleets and batches are made from ``--seed``. Each phase prints one
JSON line. ``smoke_wall_s`` is host wall time with compilation included, not
a device metric. The last line is ``{"ok": true, "device": {...}}``; off a
TPU the script exits non-zero before any phase and prints no such line.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.configs.base import ModelConfig, Segment  # noqa: E402
from repro.core import assign as assign_mod  # noqa: E402
from repro.core import cost_model as cm  # noqa: E402
from repro.core import train as gnn_train  # noqa: E402
from repro.core.graph import paper_fleet46, random_fleet  # noqa: E402
from repro.data.synthetic import SyntheticConfig, make_batch  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh_for  # noqa: E402
from repro.launch.serve import serve_batch  # noqa: E402
from repro.launch.train import train_loop  # noqa: E402
from repro.models import common as cc  # noqa: E402
from repro.models.registry import get_api  # noqa: E402
from repro.training.train_step import make_decode_step, make_prefill  # noqa: E402

ARCH = "phi3-mini-3.8b"
LOGIT_TOL = 1e-3            # planner: pallas vs jnp logits, max |diff|
CLASS_AGREE = 0.99          # planner: share of real nodes with equal argmax
# serve: max |dlogit| over std(logits), kernel vs XLA attention. bf16
# rounding alone puts the two paths about 0.07 x std apart after 32 layers on
# a v5e, and each is about as far from an f32 run of the same weights; a
# wrong mask, scale or head mapping moves the logits by O(std).
ATTN_TOL = 1e-1
LOSS_RTOL = 2e-2            # 4 chips: sharded vs one-device loss


@dataclasses.dataclass(frozen=True)
class PlannerSizes:
    train_graphs: int = 16      # plan_bench trains on 64 graphs of 16 nodes
    train_nodes: int = 16
    epochs: int = 48
    fleet_n: int = 512


@dataclasses.dataclass(frozen=True)
class ServeSizes:
    batch: int = 8
    prompt: int = 512
    gen: int = 32
    compare_steps: int = 8      # decode steps whose logits are compared


@dataclasses.dataclass(frozen=True)
class TrainSizes:
    layers: int = 4
    steps: int = 5
    global_batch: int = 8
    seq_len: int = 1024


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def _has_kernel(jitted, *args) -> bool:
    """Whether the compiled program holds a Pallas TPU kernel."""
    return "tpu_custom_call" in jitted.lower(*args).compile().as_text()


def _peak_bytes():
    """Process-wide peak device memory so far, where the backend reports it."""
    stats = jax.devices()[0].memory_stats()
    return stats.get("peak_bytes_in_use") if stats else None


def _emit(line: dict) -> None:
    print(json.dumps(line), flush=True)


def cut_depth(cfg: ModelConfig, layers: int) -> ModelConfig:
    """Same widths, ``layers`` repetitions of the (single-segment) block."""
    (seg,) = cfg.segments
    return dataclasses.replace(cfg, segments=(Segment(count=layers,
                                                      layers=seg.layers),))


# ---------------------------------------------------------------------------
# Phase 1: planner
# ---------------------------------------------------------------------------
def planner_phase(sizes: PlannerSizes, seed: int) -> dict:
    t0 = time.perf_counter()
    tasks = cm.FOUR_TASKS
    cfg = gnn_train.gnn_config_for(tasks)
    pallas_cfg = dataclasses.replace(cfg, use_pallas=True)
    floor = {t.name: t.min_memory_gb for t in tasks}
    dataset = gnn_train.make_dataset(sizes.train_graphs, tasks,
                                     n_nodes=sizes.train_nodes, seed=seed,
                                     label_frac=0.8)
    params, hist = gnn_train.train_gnn(cfg, dataset, steps=sizes.epochs,
                                       lr=0.01)
    _check(all(np.isfinite(h["loss"]) for h in hist), "GNN loss not finite")
    fleets = {46: paper_fleet46(seed), sizes.fleet_n: random_fleet(
        sizes.fleet_n, seed)}
    out = {"phase": "planner", "hidden": cfg.hidden,
           "gcn_layers": cfg.n_gcn_layers, "train_graphs": len(dataset),
           "train_nodes": sizes.train_nodes, "epochs": sizes.epochs,
           "final_loss": hist[-1]["loss"],
           "final_accuracy": hist[-1]["accuracy"], "fleets": {}}
    for n, fleet in fleets.items():
        a = assign_mod.task_assignments(fleet, tasks, params, cfg)
        placed = [i for ids in a.groups.values() for i in ids]
        _check(len(placed) == len(set(placed)),
               f"n={n}: a machine is in two groups")
        mem = fleet.memory_gb()
        _check(all(mem[ids].sum() >= floor[name]
                   for name, ids in a.groups.items()),
               f"n={n}: a group is below its task's memory floor")
        # The TPU's default f32 matmul takes bf16 passes; the kernel
        # accumulates in f32. Both paths share the dense layers around the
        # aggregation, so both run at full f32 and the kernel is what differs.
        with jax.default_matmul_precision("highest"):
            ref = gnn_train.predict_logits(params, cfg, fleet)
            got = gnn_train.predict_logits(params, pallas_cfg, fleet)
            feats, lat, node_mask = gnn_train._pad_graph(fleet)
            kernel = _has_kernel(
                gnn_train._bucketed_forward(pallas_cfg, node_mask.shape[0],
                                            feats.shape[1]),
                params, feats, lat, node_mask)
        dmax = float(np.max(np.abs(got - ref)))
        agree = float(np.mean(got.argmax(-1) == ref.argmax(-1)))
        _check(np.isfinite(got).all() and np.isfinite(ref).all(),
               f"n={n}: logits not finite")
        _check(dmax <= LOGIT_TOL,
               f"n={n}: pallas vs jnp logits differ by {dmax} > {LOGIT_TOL}")
        _check(agree >= CLASS_AGREE,
               f"n={n}: classes agree on {agree} < {CLASS_AGREE} of nodes")
        out["fleets"][str(n)] = {
            "groups": {k: len(v) for k, v in a.groups.items()},
            "deferred": a.deferred, "pallas_max_abs_dlogit": dmax,
            "class_agreement": agree, "pallas_tpu_custom_call": kernel}
    out["smoke_wall_s"] = time.perf_counter() - t0
    out["peak_bytes_in_use"] = _peak_bytes()
    return out


# ---------------------------------------------------------------------------
# Phase 2: serve
# ---------------------------------------------------------------------------
def _serve_logits(cfg, params, batch, gen, max_len, steps, use_flash):
    """Last-position prefill logits and the logits of the first ``steps``
    decode steps, every step fed the token in ``gen``. Returns
    (steps + 1, B, V) float32. Fresh closures, so nothing traced under the
    other ``use_flash`` value is reused."""
    api = get_api(cfg)
    cc.RUNTIME["use_flash"] = use_flash
    last, caches = jax.jit(make_prefill(cfg, api), static_argnums=(2,))(
        params, batch, max_len)
    decode = jax.jit(lambda p, t, pos, c: api.decode_step(p, cfg, t, pos, c),
                     donate_argnums=(3,))
    s = batch["tokens"].shape[1]
    rows = [np.asarray(last[:, -1], np.float32)]
    for i in range(steps):
        logits, caches = decode(params, jnp.asarray(gen[:, i:i + 1]),
                                jnp.int32(s + i), caches)
        rows.append(np.asarray(logits[:, -1], np.float32))
    return np.stack(rows)


def serve_phase(cfg: ModelConfig, sizes: ServeSizes, seed: int) -> dict:
    t0 = time.perf_counter()
    api = get_api(cfg)
    params = jax.jit(api.init_params, static_argnums=0)(
        cfg, jax.random.PRNGKey(seed))
    batch = {k: jnp.asarray(v) for k, v in make_batch(
        cfg, SyntheticConfig(global_batch=sizes.batch, seq_len=sizes.prompt,
                             seed=seed), 0).items()}
    gen, _ = serve_batch(cfg, params, batch, sizes.gen, log=lambda *a: None)
    _check(gen.shape == (sizes.batch, sizes.gen), f"generated {gen.shape}")
    _check(bool(((gen >= 0) & (gen < cfg.vocab_size)).all()),
           "generated token outside the vocabulary")

    # the programs serve_batch ran, compiled again (the persistent cache
    # holds them) to read their text
    max_len = sizes.prompt + sizes.gen
    prefill = make_prefill(cfg, api)
    caches = jax.eval_shape(lambda p, b: prefill(p, b, max_len),
                            params, batch)[1]
    token = jax.ShapeDtypeStruct((sizes.batch, 1), jnp.int32)
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    kernels = {
        "prefill": _has_kernel(jax.jit(prefill, static_argnums=(2,)),
                               params, batch, max_len),
        "decode": _has_kernel(jax.jit(make_decode_step(cfg, api),
                                      donate_argnums=(3,)),
                              params, token, pos, caches),
    }

    flash = cc.RUNTIME["use_flash"]
    try:
        kern = _serve_logits(cfg, params, batch, gen, max_len,
                             sizes.compare_steps, use_flash=True)
        xla = _serve_logits(cfg, params, batch, gen, max_len,
                            sizes.compare_steps, use_flash=False)
    finally:
        cc.RUNTIME["use_flash"] = flash
    _check(np.isfinite(kern).all() and np.isfinite(xla).all(),
           "serve logits not finite")
    # bounded relative to the logits' spread, per row (prefill, then each
    # decode step)
    ratio = [float(np.max(np.abs(k - x)) / np.std(k))
             for k, x in zip(kern, xla)]
    _check(max(ratio) <= ATTN_TOL,
           f"kernel vs XLA attention: max |dlogit|/std = {max(ratio)} > "
           f"{ATTN_TOL} (per row: {ratio})")
    return {"phase": "serve", "arch": cfg.name, "layers": cfg.n_layers,
            "d_model": cfg.d_model, "vocab": cfg.vocab_size,
            "dtype": cfg.dtype, "batch": sizes.batch, "prompt": sizes.prompt,
            "gen": sizes.gen, "tpu_custom_call": kernels,
            "kernel_vs_xla_max_dlogit_over_std": max(ratio),
            "kernel_vs_xla_per_row": ratio,
            "smoke_wall_s": time.perf_counter() - t0,
            "peak_bytes_in_use": _peak_bytes()}


# ---------------------------------------------------------------------------
# Phase 3: train
# ---------------------------------------------------------------------------
def train_phase(cfg: ModelConfig, sizes: TrainSizes, seed: int,
                mesh=None) -> tuple[dict, object]:
    """``sizes.steps`` steps of ``train_loop``; returns (summary, state)."""
    t0 = time.perf_counter()
    cfg = cut_depth(cfg, sizes.layers)
    state, hist = train_loop(cfg, sizes.steps, sizes.global_batch,
                             sizes.seq_len, seed=seed, log_every=1,
                             log=lambda *a: None, mesh=mesh)
    _check(len(hist) == sizes.steps, f"{len(hist)} steps logged")
    loss = [h["loss"] for h in hist]
    gnorm = [h["grad_norm"] for h in hist]
    _check(bool(np.isfinite(loss).all() and np.isfinite(gnorm).all()),
           f"non-finite loss {loss} or grad norm {gnorm}")
    n_dev = mesh.size if mesh is not None else len(jax.devices())
    return {"phase": "train", "arch": cfg.name, "layers": cfg.n_layers,
            "d_model": cfg.d_model, "vocab": cfg.vocab_size,
            "global_batch": sizes.global_batch, "seq_len": sizes.seq_len,
            "devices": n_dev, "loss": loss, "grad_norm": gnorm,
            "smoke_wall_s": time.perf_counter() - t0,
            "peak_bytes_in_use": _peak_bytes()}, state


def sharded_train_phase(cfg: ModelConfig, sizes: TrainSizes,
                        seed: int) -> dict:
    """The FSDP train step ``train_loop`` builds over every visible device,
    against the same steps on one device."""
    n = len(jax.devices())
    sharded, state = train_phase(cfg, sizes, seed)
    params = jax.tree.leaves(state.params)
    total = sum(p.size for p in params)
    big = [p for p in params if p.size * 100 >= total]   # >= 1% of params
    spread = all(len(p.sharding.device_set) == n
                 and p.sharding.shard_shape(p.shape) != p.shape for p in big)
    del state, params, big
    single, state = train_phase(cfg, sizes, seed, mesh=make_mesh_for(1))
    del state
    _check(spread, f"a large parameter leaf is not split over {n} devices")
    close = np.allclose(sharded["loss"], single["loss"], rtol=LOSS_RTOL)
    _check(bool(close), f"sharded losses {sharded['loss']} vs one device "
           f"{single['loss']} beyond rtol {LOSS_RTOL}")
    return {"phase": "train_sharded", "devices": n,
            "params_split_over_all_devices": spread,
            "loss_sharded": sharded["loss"], "loss_one_device": single["loss"],
            "smoke_wall_s_sharded": sharded["smoke_wall_s"],
            "smoke_wall_s_one_device": single["smoke_wall_s"],
            "peak_bytes_in_use": _peak_bytes()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()
    if dev[0].platform != "tpu":
        print(f"chip_smoke: no TPU (platform {dev[0].platform!r})",
              file=sys.stderr)
        return 2
    if len(dev) != args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(dev)} devices",
              file=sys.stderr)
        return 2
    enable_compile_cache()
    cfg = get_config(ARCH)

    if args.chips == 4:
        _emit(sharded_train_phase(cfg, TrainSizes(), args.seed))
    else:
        planner = planner_phase(PlannerSizes(), args.seed)
        _emit(planner)
        _check(all(f["pallas_tpu_custom_call"]
                   for f in planner["fleets"].values()),
               "no tpu_custom_call in the use_pallas predict program")
        serve = serve_phase(cfg, ServeSizes(), args.seed)
        _emit(serve)
        _check(all(serve["tpu_custom_call"].values()),
               f"no tpu_custom_call in {serve['tpu_custom_call']}")
        _emit(train_phase(cfg, TrainSizes(), args.seed)[0])
    _emit({"ok": True, "device": {"platform": dev[0].platform,
                                  "kind": dev[0].device_kind,
                                  "count": len(dev)}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
