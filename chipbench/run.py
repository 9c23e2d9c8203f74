"""Run one benchmark cell on the chips of this machine and print its result.

    python3 chipbench/run.py --workload phi3.decode --seed 7 --seconds 10 \
        --trace 0

A cell is an entry of ``workloads`` in ``BENCHMARK.json``. Its files are
found by name: ``chipbench/workloads/<cell>.json`` (entry kind, traffic,
chips), ``chipbench/configs/<config>.json`` (sizes, source, reference),
``chipbench/drivers/<kind>.py`` (set-up, one entry call, the check of what
the window produced) and ``chipbench/metrics/<metric>.py`` (one reader per
per-layer metric).

A run: set-up (weights and traffic from ``--seed``, every shape of the cell
warmed up), then a window of whole entry calls that ends with the first call
to finish after ``--seconds``, then the check of what the window produced
against the plain reference. With ``--trace 1`` the window is traced by the
JAX profiler and the result carries the per-layer metrics instead of the
end-to-end ones. The last line of standard output is one JSON object. Off a
TPU, with another number of chips than the cell asks for, or on a device kind
missing from ``chipbench/peaks.json``, the run exits with code 2 and prints
no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

TRACE_DIR = ".chipbench/trace"   # inside the checkout, git-ignored


class Refused(Exception):
    """The run cannot measure this cell here: exit 2, print no result."""


@dataclasses.dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclasses.dataclass
class Call:
    start: float
    end: float
    work: dict


@dataclasses.dataclass
class Ctx:
    """What a driver and a metric reader see of a run."""
    root: Path
    workload: dict
    config: dict
    seed: int
    seconds: float
    trace: bool
    peaks: dict
    calls: list = dataclasses.field(default_factory=list)
    traced: object = None          # trace.Trace of the traced window
    counters: dict = dataclasses.field(default_factory=dict)


# ---------------------------------------------------------------------------
# Files found by name
# ---------------------------------------------------------------------------
def bench_dir(root: Path) -> Path:
    return root / "chipbench"


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def names(root: Path, kind: str, suffix: str) -> list[str]:
    """Names of the files of one kind (workloads, configs, drivers,
    metrics), found by listing the directory."""
    d = bench_dir(root) / kind
    return sorted(p.name[:-len(suffix)] for p in d.glob("*" + suffix))


def workload(root: Path, name: str) -> dict:
    return load_json(bench_dir(root) / "workloads" / f"{name}.json")


def config(root: Path, name: str) -> dict:
    return load_json(bench_dir(root) / "configs" / f"{name}.json")


def driver(root: Path, kind: str):
    return load_module(bench_dir(root) / "drivers" / f"{kind}.py")


def reader(root: Path, metric: str):
    return load_module(bench_dir(root) / "metrics" / f"{metric}.py")


def peaks_for(root: Path, device_kind: str) -> dict:
    table = load_json(bench_dir(root) / "peaks.json")["devices"]
    if device_kind not in table:
        raise Refused(f"device kind {device_kind!r} is not in peaks.json")
    return table[device_kind]


def cell_entry(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise Refused(f"no workload {name!r} in BENCHMARK.json")


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def per_layer_for(bench: dict, cell: str) -> list:
    """Per-layer metrics of a cell: those that list it, and those with no
    list whose ``moves`` metric the cell reports."""
    e2e = {m["name"] for m in bench["end_to_end"] if applies(m, cell)}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in e2e)]


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------
def prepare(jax, root: Path, chips: int, require_tpu: bool):
    """The devices and their peaks; refuses a run off a TPU, with another
    number of chips than the cell asks for, or on an unknown device kind.
    Turns JAX's persistent compilation cache on."""
    devs = jax.devices()
    if not require_tpu:
        return devs, {}
    if devs[0].platform != "tpu":
        raise Refused(f"no TPU: JAX runs on {devs[0].platform!r}")
    if len(devs) != chips:
        raise Refused(f"the cell asks for {chips} chips, JAX sees {len(devs)}")
    peaks = peaks_for(root, devs[0].device_kind)
    # every program of the cell, however quick to compile, is kept, so that
    # only a checkout's first run compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    return devs, peaks


def memory_peak(devs) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def window(jax, drv, state, ctx: Ctx, limit: float) -> list:
    """Whole entry calls until the first one that ends ``limit`` seconds
    after the window opened. Each call runs inside a host span."""
    calls = []
    t0 = time.perf_counter()
    i = 0
    while True:
        with jax.profiler.TraceAnnotation("chipbench.call"):
            s = time.perf_counter()
            work = drv.call(state, i)
            e = time.perf_counter()
        calls.append(Call(s, e, work))
        i += 1
        if e - t0 >= limit:
            return calls


def traced_window(jax, drv, state, ctx: Ctx) -> list:
    from chipbench import trace as tr
    out = ctx.root / TRACE_DIR
    if out.exists():
        import shutil
        shutil.rmtree(out)
    opts = jax.profiler.ProfileOptions()
    # host spans come from the harness's annotations; the Python tracer
    # would slow the host it measures
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(out), create_perfetto_trace=True,
                             profiler_options=opts)
    try:
        calls = window(jax, drv, state, ctx,
                       min(ctx.seconds, ctx.workload["trace_seconds"]))
    finally:
        jax.profiler.stop_trace()
    ctx.traced = tr.load(str(out))
    return calls


def run(args, root: Path = ROOT, require_tpu: bool = True) -> dict:
    bench = load_json(root / "BENCHMARK.json")
    cell = cell_entry(bench, args.workload)
    wl = workload(root, args.workload)
    cfg = config(root, cell["config"])

    import jax
    devs, peaks = prepare(jax, root, cell["chips"], require_tpu)

    ctx = Ctx(root, wl, cfg, args.seed, args.seconds, bool(args.trace), peaks)
    drv = driver(root, wl["kind"])
    state = drv.setup(ctx)
    setup_s = time.perf_counter() - T0
    if ctx.trace:
        ctx.calls = traced_window(jax, drv, state, ctx)
    else:
        ctx.calls = window(jax, drv, state, ctx, args.seconds)
    mem = memory_peak(devs)
    drv.release(state)
    checks = drv.check(state, ctx)

    if ctx.trace:
        metrics = {}
        for m in per_layer_for(bench, args.workload):
            v = reader(root, m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        values = drv.end_to_end(ctx)
        values["setup_s"] = setup_s
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in bench["end_to_end"]
                   if applies(m, args.workload)}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": mem}
    result = {"correct": all(c.ok for c in checks),
              "attempted": sum(c.work["requests"] for c in ctx.calls),
              "failed": 0, "metrics": metrics, "device": device}
    if ctx.trace:
        from chipbench import trace as tr
        t = ctx.traced
        device["busy_s"] = tr.mean_busy_s(t)
        device["window_s"] = t.window_s
        if 0 in t.devices:
            result["breakdown"] = {"device_ops": tr.top_device_ops(t),
                                   "idle_gaps": tr.idle_gaps(t)}
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    for c in checks:
        print(f"check {c.name}: {c.value!r} (limit {c.limit!r}) "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr)
    return result


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    try:
        result = run(args)
    except Refused as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
