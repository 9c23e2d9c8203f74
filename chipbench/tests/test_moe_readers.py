"""MLA + MoE counts against hand counts at a small shape, and the readers of
the MoE cell's per-layer metrics over a small made-up trace."""
import types
from pathlib import Path

import pytest

from chipbench import counts_moe, run, trace
from chipbench.tests.test_trace import DEV, MODS, host, meta, op

ROOT = Path(__file__).resolve().parents[2]
CONFIG = dict(hidden_size=8, num_hidden_layers=3, first_k_dense_replace=1,
              num_attention_heads=2, q_lora_rank=4, kv_lora_rank=4,
              qk_nope_head_dim=2, qk_rope_head_dim=2, v_head_dim=2,
              intermediate_size=16, moe_intermediate_size=4,
              router_experts=8, n_shared_experts=1, vocab_size=32)
M = counts_moe.MlaMoe.from_config(CONFIG)


def test_params():
    # wq_a 8x4, wq_b 4x(2x4), wkv_a 8x6, wkv_b 4x(2x4), wo 4x8
    assert M.mla_params == 32 + 32 + 48 + 32 + 32
    assert M.expert_params == 3 * 8 * 4
    # 3 MLA layers, 1 dense FFN 3x8x16, 2 MoE layers of 1 shared expert and
    # an 8x8 router
    assert M.token_params == 3 * 176 + 384 + 2 * (96 + 64)


def test_absorbed_decode_attention_and_experts():
    # prompt 4, gen 3: steps over 5 and 6 filled positions, 3 layers
    w = counts_moe.absorbed_decode_attention_call(M, batch=1, prompt=4, gen=3)
    assert w.flops == 3 * 2 * 2 * 11 * (6 + 4)
    assert w.bytes == 3 * 11 * 6 * 2
    e = counts_moe.decode_experts(M, pairs=5, visits=2)
    assert (e.flops, e.bytes) == (2 * 96 * 5, 96 * 2 * 2)


def test_mla_flash_attention():
    # prompt 4: 10 causal pairs, 2 heads of (2 + 2) score and 2 value dims;
    # per token q 2x4, k_nope 2x2, k_rope 2, v and o 2x2 each; 3 layers
    w = counts_moe.mla_flash_attention(M, batch=1, seq=4)
    assert w.flops == 3 * 2 * 2 * (2 + 2 + 2) * 10
    assert w.bytes == 3 * 4 * (8 + 4 + 2 + 8) * 2


def test_serve_call_flops():
    per_token, head, expert, attn = 2 * 1232, 2 * 8 * 32, 2 * 96, 2 * 2 * 6
    prefill = 4 * per_token + head + 7 * expert + 3 * attn * 10
    decode = 2 * (per_token + head) + 3 * expert + 3 * attn * 11
    assert counts_moe.serve_call_flops(M, 1, 4, 3, 7, 3) == prefill + decode


@pytest.fixture
def ctx():
    """Two decode steps [0, 100) and [100, 200) and a prefill [200, 300):
    each step holds a grouped matmul (no scope) of 20 us, the scan's copy of
    an expert array out of the stacked weights of 10 us and a route op of
    5 us; the prefill holds a grouped matmul of 50 us and a flash kernel of
    20 us."""
    step = "jit(serve_step)/while/body/closed_call/mlp"
    events = meta()
    for t0 in (0, 100):
        events += [op(t0 + 10, 20, "ragged-dot-none.3"),
                   op(t0 + 40, 5, "sort.1", f"{step}/moe_route/sort"),
                   op(t0 + 60, 10, "dynamic-slice_bitcast_fusion.9",
                      "jit(serve_step)/while/body/squeeze:"),
                   {"ph": "X", "pid": DEV, "tid": MODS, "ts": t0, "dur": 100,
                    "name": "jit_serve_step(1)"}]
    events += [op(210, 50, "ragged-dot-none.1"),
               op(265, 20, "flash_attention.3", "jit(prefill_step)/checkpoint/"
                  "attn/jit(flash_attention)/pallas_call:"),
               {"ph": "X", "pid": DEV, "tid": MODS, "ts": 200, "dur": 100,
                "name": "jit_prefill_step(2)"},
               host(0, 300, trace.CALL_SPAN)]
    work = {"requests": 1, "tokens": 3, "moe_pairs_prefill": 7,
            "moe_pairs_decode": 3, "moe_expert_visits_decode": 2}
    return types.SimpleNamespace(
        traced=trace.parse(events), config=CONFIG,
        peaks={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9},
        workload={"traffic": {"batch": 1, "prompt": 4, "gen": 3}},
        calls=[run.Call(0.0, 3e-4, work)])


def test_moe_experts_roofline_reads_the_decode_steps_expert_layer(ctx):
    # 384 bytes at 1e9 B/s over 60 us of the two steps' grouped matmuls and
    # the copies that feed them
    value = run.reader(ROOT, "moe_experts_roofline").read(ctx)
    assert value == pytest.approx(100 * 384e-9 / 60e-6)


def test_mla_flash_attention_roofline(ctx):
    # 528 bytes at 1e9 B/s (720 FLOP at 1e12 take less) over 20 us
    value = run.reader(ROOT, "mla_flash_attention_roofline").read(ctx)
    assert value == pytest.approx(100 * 528e-9 / 20e-6)


def test_moe_route_ms_per_decode_step(ctx):
    assert run.reader(ROOT, "moe_route_ms").read(ctx) == pytest.approx(5e-3)


def test_moe_serve_mfu(ctx):
    flops = counts_moe.serve_call_flops(M, 1, 4, 3, 7, 3)
    value = run.reader(ROOT, "moe_serve_mfu").read(ctx)
    assert value == pytest.approx(100 * flops / (300e-6 * 1e12))


def test_readers_read_nothing_without_the_counters(ctx):
    ctx.calls = [run.Call(0.0, 3e-4, {"requests": 1, "tokens": 3})]
    assert run.reader(ROOT, "moe_experts_roofline").read(ctx) is None
    assert run.reader(ROOT, "moe_serve_mfu").read(ctx) is None
