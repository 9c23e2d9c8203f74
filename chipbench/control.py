"""Read a cell's compared numbers for the program and for its control over
many seeds in one process, to set the cell's limits from.

    python3 chipbench/control.py --workload phi3.decode --seeds 101-112 \
        --control-seeds 3 --seconds 1

For each seed: set-up without the warm-up call, a window of ``--seconds``
(at least one whole call, at the cell's own load), then the numbers the run
compares, for the program and, on the first ``--control-seeds`` seeds, for
the control: the plain reference computed one precision step lower and put
in the program's place. Each side's numbers are held to the cell's limits
as a run holds them, and its verdict is ``correct`` on its line: the
program's has to come out true and the control's false. One JSON line per
seed goes to standard output and to ``chiprun_out/control/<cell>.jsonl``.
Needs the cell's chips, like run.py.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from chipbench import run as R  # noqa: E402


def seeds(spec: str) -> list[int]:
    out = []
    for part in spec.split(","):
        lo, _, hi = part.partition("-")
        out.extend(range(int(lo), int(hi or lo) + 1))
    return out


def verdict(numbers: dict, checks: list) -> dict:
    """``numbers`` held to the limits of the run's own ``checks``, with
    ``correct`` as a run would print it."""
    held = [R.Check(c.name, numbers[c.name], c.limit) for c in checks]
    return dict(numbers, correct=all(c.ok for c in held))


def main(argv=None, root: Path = ROOT, require_tpu: bool = True) -> list:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)
    bench = R.load_json(root / "BENCHMARK.json")
    cell = R.cell_entry(bench, args.workload)
    wl = R.workload(root, args.workload)
    cfg = R.config(root, cell["config"])
    import jax
    _, peaks = R.prepare(jax, root, cell["chips"], require_tpu)
    drv = R.driver(root, wl["kind"])
    out_dir = root / "chiprun_out" / "control"
    out_dir.mkdir(parents=True, exist_ok=True)
    lines = []
    for k, seed in enumerate(seeds(args.seeds)):
        t0 = time.perf_counter()
        ctx = R.Ctx(root, wl, cfg, seed, args.seconds, False, peaks)
        state = drv.setup(ctx, warm=False)
        ctx.calls = R.window(jax, drv, state, ctx, args.seconds)
        drv.release(state)
        checks = drv.check(state, ctx)
        line = {"seed": seed, "calls": len(ctx.calls),
                "program": verdict({c.name: c.value for c in checks}, checks)}
        if k < args.control_seeds:
            line["control"] = verdict(drv.control(state, ctx), checks)
        line["seconds"] = time.perf_counter() - t0
        print(json.dumps(line), flush=True)
        with open(out_dir / f"{args.workload}.jsonl", "a") as f:
            f.write(json.dumps(line) + "\n")
        lines.append(line)
        del state
        gc.collect()
    return lines


if __name__ == "__main__":
    main()
