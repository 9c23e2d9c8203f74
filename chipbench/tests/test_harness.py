"""Discovery by file name, the peaks table, and runs off a TPU."""
import json
import os
import subprocess
import sys

import pytest

from chipbench import run
from chipbench.tests.helpers import ROOT, args, tiny_copy


def test_every_cell_and_metric_has_its_files():
    bench = run.load_json(ROOT / "BENCHMARK.json")
    assert {w["name"] for w in bench["workloads"]} <= set(
        run.names(ROOT, "workloads", ".json"))
    assert {c["name"] for c in bench["configs"]} <= set(
        run.names(ROOT, "configs", ".json"))
    assert {m["name"] for m in bench["per_layer"]} <= set(
        run.names(ROOT, "metrics", ".py"))
    for w in bench["workloads"]:
        wl = run.workload(ROOT, w["name"])
        assert wl["kind"] in run.names(ROOT, "drivers", ".py")
        assert (wl["config"], wl["chips"], wl["why"]) == (
            w["config"], w["chips"], w["why"])
    for c in bench["configs"]:
        assert ROOT / c["file"] == ROOT / "chipbench" / "configs" / (
            c["name"] + ".json")


def test_new_files_are_found_without_edits(tmp_path):
    """A cell, a configuration and a per-layer metric added as new files
    are found by name; no existing file changes."""
    root = tiny_copy(tmp_path)
    b = root / "chipbench"
    cfg = json.loads((b / "configs" / "phi3-mini-3.8b.json").read_text())
    (b / "configs" / "phi3-tiny.json").write_text(json.dumps(cfg))
    wl = json.loads((b / "workloads" / "phi3.decode.json").read_text())
    wl.update(name="phi3-tiny.decode", config="phi3-tiny")
    (b / "workloads" / "phi3-tiny.decode.json").write_text(json.dumps(wl))
    (b / "metrics" / "calls_traced.py").write_text(
        "def read(ctx):\n    return len(ctx.calls)\n")
    before = {p: p.read_bytes() for p in b.rglob("*") if p.is_file()
              and p.name not in ("phi3-tiny.json", "phi3-tiny.decode.json",
                                 "calls_traced.py")}
    assert "phi3-tiny.decode" in run.names(root, "workloads", ".json")
    assert "phi3-tiny" in run.names(root, "configs", ".json")
    assert "calls_traced" in run.names(root, "metrics", ".py")
    bench = run.load_json(root / "BENCHMARK.json")
    bench["workloads"].append({"name": "phi3-tiny.decode",
                               "config": "phi3-tiny", "traffic": "t",
                               "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "calls_traced", "unit": "count",
                               "better": "higher", "source": "host_clock",
                               "layer": "harness",
                               "moves": "serve_tokens_per_s",
                               "workloads": ["phi3-tiny.decode"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    out = run.run(args("phi3-tiny.decode", trace=1), root=root,
                  require_tpu=False)
    assert out["correct"] and out["metrics"]["calls_traced"]["value"] >= 1
    assert all(p.read_bytes() == v for p, v in before.items())


def test_unknown_device_kind_is_refused():
    with pytest.raises(run.Refused):
        run.peaks_for(ROOT, "TPU v99 imaginary")
    assert run.peaks_for(ROOT, "TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_cpu_run_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(ROOT / "chipbench" / "run.py"),
                        "--workload", "phi3.decode", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_benchmark_alone_exits_nonzero(tmp_path):
    """A directory with only BENCHMARK.json and chipbench/ has no program."""
    root = tiny_copy(tmp_path)
    env = {"PATH": os.environ["PATH"], "JAX_PLATFORMS": "cpu",
           "HOME": str(tmp_path)}
    p = subprocess.run([sys.executable, str(root / "chipbench" / "run.py"),
                        "--workload", "phi3.decode", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       cwd=root, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


@pytest.mark.parametrize("cell", ["phi3.decode", "starcoder2.decode"])
def test_tiny_run_is_correct(tmp_path, cell):
    root = tiny_copy(tmp_path)
    out = run.run(args(cell), root=root, require_tpu=False)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and set(out["metrics"]) >= {"setup_s"}
    assert list(out)[-1] == "checks"
