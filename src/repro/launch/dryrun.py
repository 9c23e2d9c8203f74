import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST be the first lines — jax locks the device count on first init.
# The dry-run (and ONLY the dry-run) runs with 512 placeholder host devices
# so launch.mesh can build the production meshes.

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this:
  1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  2. materializes ShapeDtypeStruct stand-ins for the step inputs
     (launch.specs — no device allocation),
  3. jax.jit(step, in_shardings=..., out_shardings=...).lower(...).compile(),
  4. prints compiled.memory_analysis() (proves it fits) and
     cost_analysis() (FLOPs/bytes for SSRoofline),
  5. parses the optimized HLO for collective bytes and writes the roofline
     JSON consumed by EXPERIMENTS.md.

Usage:
  python -m repro.launch.dryrun --arch qwen3-32b --shape train_4k
  python -m repro.launch.dryrun --arch all --shape all --multi-pod --out d/
"""
import argparse
import dataclasses
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis import hlo_cost
from repro.analysis.roofline import (HW, active_params, collective_bytes,
                                     model_flops, roofline_report)
from repro.configs import ARCHS, SHAPES, get_config, shape_applicable
from repro.launch import specs as sp
from repro.launch.mesh import make_production_mesh
from repro.models import common as cc
from repro.models.registry import get_api
from repro.parallel.sharding import (SEQ_PARALLEL_ACT_RULES, ShardingRules,
                                     activation_resolver)
from repro.training.optimizer import AdamWConfig
from repro.training.train_step import (make_decode_step, make_prefill,
                                       make_train_step)

# Params big enough that serving must FSDP the weights over `data` too
# (won't fit model-axis TP alone in 16 GB HBM).
_SERVE_FSDP_BYTES = 8e9 * 16   # 8 GB/device x model axis


def _ns_tree(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def _mem_dict(mem) -> dict:
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "generated_code_size_in_bytes",
            "alias_size_in_bytes", "peak_memory_in_bytes")
    out = {}
    for k in keys:
        v = getattr(mem, k, None)
        if v is not None:
            out[k] = int(v)
    return out


def run_cell(arch: str, shape_name: str, multi_pod: bool, runtime: cc.Runtime,
             opt_overrides: dict | None = None, verbose: bool = True,
             save_hlo: str = "") -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    result = {"arch": arch, "shape": shape_name,
              "mesh": "2x16x16" if multi_pod else "16x16"}
    if not ok:
        result["skipped"] = why
        return result
    if shape.kind == "decode" and cfg.family == "audio" \
            and shape_name == "long_500k":
        result["skipped"] = "audio long_500k (full attention)"
        return result

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = int(np.prod(list(mesh.shape.values())))

    t0 = time.time()
    api = get_api(cfg)
    n_active = active_params(cfg)
    result["active_params"] = n_active

    if shape.kind == "train":
        param_bytes = n_active * 2   # rough bf16 (active ~ total for dense)
        # exact total for the moment heuristic:
        struct_p = sp.param_struct(cfg)
        total_params = sum(float(np.prod(l.shape))
                           for l in jax.tree.leaves(struct_p))
        moment_dtype = "bfloat16" if total_params * 2 > 100e9 else "float32"
        opt_cfg = AdamWConfig(moment_dtype=moment_dtype,
                              **(opt_overrides or {}))
        rules = ShardingRules(mesh=mesh, fsdp=True)
        state_struct = sp.train_state_struct(cfg, opt_cfg)
        state_sh = _ns_tree(mesh, sp.train_state_specs(rules, state_struct))
        batch = sp.input_specs(cfg, shape)
        batch_sh = _ns_tree(mesh, sp.batch_partition_specs(rules, batch))
        step = make_train_step(cfg, opt_cfg, api)
        cc.push_logical_rules(activation_resolver(rules))
        try:
            with mesh, cc.use_runtime(runtime):
                jitted = jax.jit(step, in_shardings=(state_sh, batch_sh),
                                 out_shardings=(state_sh, None),
                                 donate_argnums=(0,))
                lowered = jitted.lower(state_struct, batch)
        finally:
            cc.pop_logical_rules()
        tokens = shape.global_batch * shape.seq_len
        mflops = model_flops(n_active, tokens, "train")

    elif shape.kind == "prefill":
        struct_p = sp.param_struct(cfg)
        total_params = sum(float(np.prod(l.shape))
                           for l in jax.tree.leaves(struct_p))
        fsdp = total_params * 2 > _SERVE_FSDP_BYTES
        rules = ShardingRules(mesh=mesh, fsdp=fsdp)
        params_sh = _ns_tree(mesh, sp.param_specs(rules, struct_p))
        batch = sp.input_specs(cfg, shape)
        batch_sh = _ns_tree(mesh, sp.batch_partition_specs(rules, batch))
        prefill_fn = make_prefill(cfg, api)
        # vlm prepends n_patches positions to the text tokens
        max_len = shape.seq_len + (cfg.n_patches if cfg.family == "vlm" else 0)
        cc.push_logical_rules(activation_resolver(rules))
        try:
            with mesh, cc.use_runtime(runtime):
                jitted = jax.jit(prefill_fn,
                                 in_shardings=(params_sh, batch_sh),
                                 static_argnums=(2,))
                lowered = jitted.lower(struct_p, batch, max_len)
        finally:
            cc.pop_logical_rules()
        tokens = shape.global_batch * shape.seq_len
        mflops = model_flops(n_active, tokens, "train") / 3.0   # fwd only

    else:  # decode
        struct_p = sp.param_struct(cfg)
        total_params = sum(float(np.prod(l.shape))
                           for l in jax.tree.leaves(struct_p))
        fsdp = total_params * 2 > _SERVE_FSDP_BYTES
        act_rules = SEQ_PARALLEL_ACT_RULES if shape.global_batch < 8 else None
        rules = ShardingRules(mesh=mesh, fsdp=fsdp, act_rules=act_rules)
        params_sh = _ns_tree(mesh, sp.param_specs(rules, struct_p))
        b = shape.global_batch
        max_len = shape.seq_len
        caches = sp.decode_cache_struct(cfg, b, max_len)
        caches_sh = _ns_tree(mesh, sp.decode_cache_specs(rules, cfg, b,
                                                         max_len))
        token = jax.ShapeDtypeStruct((b, 1), jnp.int32)
        token_sh = NamedSharding(mesh, sp.token_specs(rules, b))
        pos = jax.ShapeDtypeStruct((), jnp.int32)
        pos_sh = NamedSharding(mesh, P())
        serve = make_decode_step(cfg, api)
        cc.push_logical_rules(activation_resolver(rules))
        try:
            with mesh, cc.use_runtime(runtime):
                jitted = jax.jit(
                    serve,
                    in_shardings=(params_sh, token_sh, pos_sh, caches_sh),
                    out_shardings=(token_sh, caches_sh),
                    donate_argnums=(3,))
                lowered = jitted.lower(struct_p, token, pos, caches)
        finally:
            cc.pop_logical_rules()
        tokens = float(b)
        mflops = model_flops(n_active, tokens, "decode")

    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    xla_cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    if save_hlo:
        with open(save_hlo, "w") as f:
            f.write(hlo)
    # loop-aware per-device cost (XLA's cost_analysis counts while bodies
    # once — see analysis.hlo_cost)
    loop_cost = hlo_cost.analyze(hlo)
    coll = loop_cost["collectives"]
    roof = roofline_report(loop_cost, coll, n_chips, mflops)

    result.update({
        "ok": True,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory": _mem_dict(mem),
        "cost": {"flops": loop_cost["flops"], "bytes": loop_cost["bytes"],
                 "unknown_trip_loops": loop_cost["unknown_trip_loops"],
                 "xla_flops_unscaled": float(xla_cost.get("flops", 0.0)),
                 "bytes_by_kind": loop_cost.get("bytes_by_kind", {})},
        "collectives": coll,
        "roofline": roof,
        "runtime": dataclasses.asdict(runtime),
    })
    if verbose:
        print(f"== {arch} x {shape_name} on {result['mesh']} ==")
        print("memory_analysis:", mem)
        print("loop-aware flops/bytes per device:",
              loop_cost["flops"], loop_cost["bytes"])
        print("collective bytes:", coll["total"],
              {k: int(v) for k, v in coll["per_kind"].items() if v})
        print("roofline:", json.dumps(roof["seconds"]),
              "bottleneck:", roof["bottleneck"],
              "roofline_fraction:", roof.get("roofline_fraction"))
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="")
    ap.add_argument("--seq-chunk", type=int, default=256)
    args = ap.parse_args(argv)

    archs = ARCHS if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    runtime = cc.Runtime(seq_chunk=args.seq_chunk)

    results = []
    n_fail = 0
    for arch in archs:
        for shape_name in shapes:
            for mp in meshes:
                try:
                    r = run_cell(arch, shape_name, mp, runtime)
                except Exception as e:  # a cell failure is a bug — surface it
                    traceback.print_exc()
                    r = {"arch": arch, "shape": shape_name,
                         "mesh": "2x16x16" if mp else "16x16",
                         "error": repr(e)}
                    n_fail += 1
                results.append(r)
                if args.out:
                    os.makedirs(args.out, exist_ok=True)
                    name = f"{r['arch']}__{r['shape']}__{r['mesh']}.json"
                    with open(os.path.join(args.out, name), "w") as f:
                        json.dump(r, f, indent=1)
    ok = sum(1 for r in results if r.get("ok"))
    skipped = sum(1 for r in results if "skipped" in r)
    print(f"\nDRYRUN SUMMARY: {ok} ok, {skipped} skipped, {n_fail} failed, "
          f"{len(results)} total")
    return 1 if n_fail else 0


if __name__ == "__main__":
    sys.exit(main())
