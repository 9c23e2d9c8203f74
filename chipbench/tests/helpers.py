"""A copy of the benchmark, cut to CPU size, in a temporary directory."""
import json
import shutil
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

TINY_DECODER = dict(hidden_size=256, intermediate_size=512,
                    num_hidden_layers=2, num_attention_heads=4, vocab_size=256)
# limits for these sizes, set as the cells' own are: over 18 seeds on the
# CPU the program read at most 0.0096 (phi3) and 0.0035 (starcoder2), the
# float8 control at least 0.048 and 0.032. At smaller widths the control
# puts the reference's own tokens first on some seeds and reads 0.
TINY_LIMITS = {
    "serve": {"served_token_gap": 0.02},
}
TINY_TRAFFIC = {
    "serve": {"batch": 4, "prompt": 24, "gen": 8, "pool": 2},
}


def tiny_copy(tmp: Path) -> Path:
    """BENCHMARK.json and chipbench/ under ``tmp``, every configuration and
    workload cut to a size the CPU runs in seconds."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp / "BENCHMARK.json")
    shutil.copytree(ROOT / "chipbench", tmp / "chipbench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    for f in (tmp / "chipbench" / "configs").glob("*.json"):
        c = json.loads(f.read_text())
        if c.get("reference") == "dense_decoder":
            kv = 4 if c["num_key_value_heads"] == c["num_attention_heads"] else 2
            c.update(TINY_DECODER, num_key_value_heads=kv)
            c.pop("program_arch", None)
        f.write_text(json.dumps(c))
    for f in (tmp / "chipbench" / "workloads").glob("*.json"):
        w = json.loads(f.read_text())
        w["traffic"] = TINY_TRAFFIC[w["kind"]]
        w["limits"] = TINY_LIMITS[w["kind"]]
        f.write_text(json.dumps(w))
    return tmp


def args(cell: str, seed: int = 2 ** 31 + 11, seconds: float = 0.2,
         trace: int = 0):
    from chipbench import run
    return run.parse_args(["--workload", cell, "--seed", str(seed),
                           "--seconds", str(seconds), "--trace", str(trace)])
