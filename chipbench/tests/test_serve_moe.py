"""The ``serve_moe`` driver at CPU size: the DeepSeek-V2 share cell runs
through ``serve_batch`` traced and untraced and comes out correct; a fault
planted in the MoE or MLA path makes it not correct; the float8 control
fails where the program passes."""
import dataclasses
import json

import jax.numpy as jnp
import pytest

import repro.models.attention as attn_mod
import repro.models.mlp as mlp_mod
from chipbench import control, run
from chipbench.conftest import TINY_MLA_MOE, TINY_MOE_CHECK
from chipbench.tests.helpers import args, tiny_copy

CELL = "deepseek_v2.decode"


def tiny_moe_copy(tmp):
    """``tiny_copy`` with every MLA + MoE configuration cut to
    ``TINY_MLA_MOE`` and its workloads' check to ``TINY_MOE_CHECK``."""
    root = tiny_copy(tmp)
    for f in (root / "chipbench" / "configs").glob("*.json"):
        c = json.loads(f.read_text())
        if c.get("reference") == "mla_moe_decoder":
            c.update(TINY_MLA_MOE)
            c.pop("program_arch", None)
            f.write_text(json.dumps(c))
    for f in (root / "chipbench" / "workloads").glob("*.json"):
        w = json.loads(f.read_text())
        if w["kind"] == "serve_moe":
            w["check"].update(TINY_MOE_CHECK)
            f.write_text(json.dumps(w))
    return root


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_at_cpu_size(tmp_path, trace):
    out = run.run(args(CELL, trace=trace), root=tiny_moe_copy(tmp_path),
                  require_tpu=False)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0
    if not trace:
        assert out["metrics"]["serve_tokens_per_s"]["value"] > 0


def _drop_held_expert(orig):
    def select_experts(router_w, x, spec):
        probs, w, e = orig(router_w, x, spec)
        return probs, jnp.where(e == spec.first_local, 0.0, w), e
    return select_experts


def _latent_unwritten(orig):
    def mla_decode(p, spec, x, pos, cache, layer=None):
        y, _ = orig(p, spec, x, pos, cache, layer=layer)
        return y, cache
    return mla_decode


def _gates_renormalised(orig):
    def moe_dropless(p, spec, x, act):
        return orig(p, dataclasses.replace(spec, norm_topk=True), x, act)
    return moe_dropless


FAULTS = {
    "held_expert_output_dropped": (mlp_mod, "select_experts",
                                   _drop_held_expert),
    "latent_row_not_written": (attn_mod, "mla_decode", _latent_unwritten),
    "gates_renormalised": (mlp_mod, "moe_dropless", _gates_renormalised),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_moe_fault_is_caught(tmp_path, monkeypatch, fault):
    mod, name, wrap = FAULTS[fault]
    monkeypatch.setattr(mod, name, wrap(getattr(mod, name)))
    out = run.run(args(CELL), root=tiny_moe_copy(tmp_path), require_tpu=False)
    assert not out["correct"], out["checks"]


def test_control_reads_above_the_program(tmp_path):
    lines = control.main(["--workload", CELL, "--seeds", "5-7",
                          "--control-seeds", "3", "--seconds", "0.05"],
                         root=tiny_moe_copy(tmp_path), require_tpu=False)
    assert all(x["program"]["correct"] for x in lines), lines
    assert not any(x["control"]["correct"] for x in lines), lines
    program = max(x["program"]["served_token_miss_share"] for x in lines)
    ctrl = min(x["control"]["served_token_miss_share"] for x in lines)
    assert ctrl > 3 * program, (program, ctrl)
