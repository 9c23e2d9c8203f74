"""chip_smoke.py on the CPU: its phases at smoke size (kernels interpreted),
its refusal to run without a TPU, and where the compile cache goes."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

from repro.configs import get_config, reduce_for_smoke

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke as cs  # noqa: E402

TRAIN = cs.TrainSizes(layers=2, steps=3, global_batch=4, seq_len=32)


def _cfg():
    return reduce_for_smoke(get_config(cs.ARCH))


def _run(args, env_extra=None, cwd=ROOT, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    for key, value in (env_extra or {}).items():
        if value is None:
            env.pop(key, None)
        else:
            env[key] = value
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_planner_phase_smoke():
    out = cs.planner_phase(cs.PlannerSizes(train_graphs=4, epochs=5,
                                           fleet_n=64), seed=0)
    assert set(out["fleets"]) == {"46", "64"}
    for fleet in out["fleets"].values():
        assert fleet["pallas_max_abs_dlogit"] <= cs.LOGIT_TOL
        assert fleet["class_agreement"] >= cs.CLASS_AGREE
        assert fleet["groups"]


def test_serve_phase_smoke():
    out = cs.serve_phase(_cfg(), cs.ServeSizes(batch=2, prompt=16, gen=6,
                                               compare_steps=4), seed=0)
    assert len(out["kernel_vs_xla_per_row"]) == 5   # prefill + 4 decode steps
    assert out["kernel_vs_xla_max_dlogit_over_std"] <= cs.ATTN_TOL


def test_train_phase_smoke():
    out, state = cs.train_phase(_cfg(), TRAIN, seed=0)
    assert out["layers"] == TRAIN.layers and len(out["loss"]) == TRAIN.steps
    assert state.opt.step == TRAIN.steps


def test_sharded_train_phase_on_four_cpu_devices():
    script = (f"import json, chip_smoke as cs\n"
              f"from repro.configs import get_config, reduce_for_smoke\n"
              f"cfg = reduce_for_smoke(get_config(cs.ARCH))\n"
              f"print(json.dumps(cs.sharded_train_phase(cfg, cs.{TRAIN!r}, "
              f"0)))\n")
    res = _run(["-c", script], {
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        "PYTHONPATH": str(ROOT / "src")})
    assert res.returncode == 0, res.stderr[-4000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["devices"] == 4 and out["params_split_over_all_devices"]


def test_exits_without_tpu():
    res = _run(["chip_smoke.py"])
    assert res.returncode != 0
    assert '"ok"' not in res.stdout


def test_exits_outside_the_repo(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    res = _run(["chip_smoke.py"], cwd=tmp_path)
    assert res.returncode != 0
    assert '"ok"' not in res.stdout


def test_compile_cache_uses_the_env_dir(tmp_path):
    script = ("import jax, jax.numpy as jnp\n"
              "from repro.launch.compile_cache import enable_compile_cache\n"
              "print(enable_compile_cache())\n"
              "jax.jit(lambda x: x @ x)(jnp.ones((8, 8))).block_until_ready()\n")
    res = _run(["-c", script], {"JAX_COMPILATION_CACHE_DIR": str(tmp_path),
                                "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS":
                                    "0",
                                "PYTHONPATH": str(ROOT / "src")})
    assert res.returncode == 0, res.stderr[-4000:]
    assert res.stdout.strip() == str(tmp_path)
    assert any(tmp_path.iterdir())


def test_compile_cache_defaults_to_the_repo():
    script = ("import jax\n"
              "from repro.launch.compile_cache import enable_compile_cache\n"
              "print(enable_compile_cache())\n"
              "print(jax.config.jax_compilation_cache_dir)\n")
    res = _run(["-c", script], {"JAX_COMPILATION_CACHE_DIR": None,
                                "PYTHONPATH": str(ROOT / "src")})
    assert res.returncode == 0, res.stderr[-4000:]
    assert res.stdout.split() == [str(ROOT / ".jax_cache")] * 2
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
