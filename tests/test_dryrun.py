"""Dry-run integration: lower+compile representative cells on the production
meshes in a subprocess (512 forced host devices must not leak into the main
test process)."""
import json
import os
import subprocess
import sys

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
import sys, json
sys.path.insert(0, r"%(src)s")
from repro.launch.dryrun import run_cell
from repro.models.common import Runtime
runtime = Runtime(seq_chunk=256)
out = []
# one train cell on the single-pod mesh, one decode cell on the multi-pod
# mesh, one audio prefill (covers the three lowering paths + cache specs)
for arch, shape, mp in [("xlstm-125m", "train_4k", False),
                        ("gemma3-1b", "decode_32k", True),
                        ("whisper-small", "prefill_32k", False),
                        ("qwen3-32b", "long_500k", False)]:
    r = run_cell(arch, shape, mp, runtime, verbose=False)
    out.append({k: r.get(k) for k in ("arch", "shape", "mesh", "ok",
                                      "skipped")})
print("JSON:" + json.dumps(out))
"""


def test_dryrun_cells():
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run([sys.executable, "-c", SCRIPT % {"src": src}],
                         capture_output=True, text=True, env=env,
                         timeout=1800)
    line = [l for l in res.stdout.splitlines() if l.startswith("JSON:")]
    assert line, res.stdout + res.stderr
    cells = json.loads(line[0][5:])
    by_key = {(c["arch"], c["shape"]): c for c in cells}
    assert by_key[("xlstm-125m", "train_4k")]["ok"]
    assert by_key[("gemma3-1b", "decode_32k")]["ok"]
    assert by_key[("gemma3-1b", "decode_32k")]["mesh"] == "2x16x16"
    assert by_key[("whisper-small", "prefill_32k")]["ok"]
    # long_500k on a pure full-attention arch must be a DOCUMENTED skip
    assert "skipped" in by_key[("qwen3-32b", "long_500k")]
