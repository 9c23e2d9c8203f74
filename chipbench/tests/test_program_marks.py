"""The readers of what the serving path marks: ``decode_unscoped_ms`` on the
step program's named scopes, ``serve_lowerings`` and ``serve_compile_ms`` on
JAX's own compile spans on the host."""
import dataclasses
import re
import types

import jax
import jax.numpy as jnp
import pytest

from chipbench import run, trace
from chipbench.tests.helpers import ROOT, TINY_TRAFFIC, args, tiny_copy
from chipbench.tests.test_trace import DATA, DEV, MODS, host, meta, op

SCOPES = ("embed", "norm", "attn", "mlp", "lm_head", "sample")


def _read(metric, events):
    return run.reader(ROOT, metric).read(
        types.SimpleNamespace(traced=trace.parse(meta() + events)))


# ---------------------------------------------------------------------------
# decode_unscoped_ms
# ---------------------------------------------------------------------------
def _module(ts, dur, name):
    return {"ph": "X", "pid": DEV, "tid": MODS, "ts": ts, "dur": dur,
            "name": name}


STEP_OPS = [
    # first step run [0, 100): a while [0, 90) holding a scoped matmul and
    # the scan's slice, then a layout copy with no scope at all
    _module(0, 100, "jit_serve_step(7)"),
    op(0, 90, "while.1", "jit(serve_step)/while"),
    op(10, 10, "fusion.2", "jit(serve_step)/while/body/closed_call/attn/dot"),
    op(20, 10, "dynamic-slice.3", "jit(serve_step)/while/body/dynamic_slice"),
    op(92, 4, "copy.4", ""),
    # a prefill run: outside every step run
    _module(120, 20, "jit_prefill_step(8)"),
    op(120, 10, "fusion.5", "jit(prefill_step)/while/body/dynamic_slice"),
    # second step run [200, 250)
    _module(200, 50, "jit_serve_step(7)"),
    op(205, 10, "fusion.6", "jit(serve_step)/while/body/dynamic_update_slice"),
    op(220, 20, "fusion.7", "jit(serve_step)/sample/argmax"),
    host(0, 300, trace.CALL_SPAN),
]


def test_decode_unscoped_ms_reads_a_hand_built_trace():
    # unscoped self time: while 70, slice 10, copy 4; then 10; over 2 runs
    assert _read("decode_unscoped_ms", STEP_OPS) == pytest.approx(
        (70 + 10 + 4 + 10) / 2 / 1e3)


def test_decode_unscoped_ms_is_silent_without_scopes():
    unscoped = [e for e in STEP_OPS
                if e["name"] not in ("fusion.2", "fusion.7")]
    assert _read("decode_unscoped_ms", unscoped) is None
    assert _read("decode_unscoped_ms", [host(0, 10, trace.CALL_SPAN)]) is None
    reader = run.reader(ROOT, "decode_unscoped_ms")
    assert reader.read(types.SimpleNamespace(traced=None)) is None
    # two decode steps of the program before the scopes, on a TPU v5e
    recorded = trace.load(str(DATA))
    assert reader.read(types.SimpleNamespace(traced=recorded)) is None


def test_decode_unscoped_ms_scopes_match_the_compiled_step():
    """What the reader counts as scoped is what the program's compiled
    decode step puts under its named scopes; the scan's slicing is not."""
    from repro.configs import get_config, reduce_for_smoke
    from repro.models.registry import get_api
    from repro.training.train_step import make_decode_step, make_prefill
    cfg = dataclasses.replace(reduce_for_smoke(get_config("phi3-mini-3.8b")),
                              remat=False)
    api = get_api(cfg)
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jnp.ones((2, 8), jnp.int32)
    _, caches = jax.jit(make_prefill(cfg, api), static_argnums=(2,))(
        params, {"tokens": tokens}, 12)
    text = jax.jit(make_decode_step(cfg, api)).lower(
        params, tokens[:, :1], jnp.int32(8), caches).compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', text)
    reader = run.reader(ROOT, "decode_unscoped_ms")
    assert not reader.scoped("jit(serve_step)/while/body/dynamic_slice")
    under = [n for n in names if n.startswith("jit(serve_step)/")
             and any(f"/{s}/" in n for s in SCOPES)]
    assert under and all(reader.scoped(n) for n in under)


# ---------------------------------------------------------------------------
# serve_lowerings, serve_compile_ms
# ---------------------------------------------------------------------------
# two calls of 100 us. The first makes a program: a jitted call [10, 60)
# (its span doubled, as JAX writes it) traces, lowers [20, 40) and first
# launches it at 55; a second jitted call [70, 90) only runs. The second call
# lowers [130, 150) in a call whose launch span is missing, so its interval
# runs to the jitted span's end, 160.
COMPILES = [
    host(0, 100, trace.CALL_SPAN),
    host(10, 50, "PjitFunction(prefill_step)"),
    host(10, 49, "PjitFunction(prefill_step)"),
    host(12, 3, "PjitFunction(multiply)"),
    host(20, 20, "lower_sharding_computation"),
    host(55, 4, "ExecuteReplicated.__call__"),
    host(70, 20, "PjitFunction(serve_step)"),
    host(100, 100, trace.CALL_SPAN),
    host(120, 40, "PjitFunction(serve_step)"),
    host(130, 20, "lower_sharding_computation"),
    host(150, 5, "PjitFunction(_argmax)"),
]


def test_serve_compile_readers_read_a_hand_built_trace():
    assert _read("serve_lowerings", COMPILES) == 1.0
    # (55 - 10) + (160 - 120) us over two calls
    assert _read("serve_compile_ms", COMPILES) == pytest.approx(
        (45 + 40) / 2 / 1e3)


def test_serve_compile_readers_read_zero_when_programs_are_kept():
    kept = [e for e in COMPILES if e["name"] != "lower_sharding_computation"]
    assert _read("serve_lowerings", kept) == 0
    assert _read("serve_compile_ms", kept) == 0
    for metric in ("serve_lowerings", "serve_compile_ms"):
        assert _read(metric, []) is None
        reader = run.reader(ROOT, metric)
        assert reader.read(types.SimpleNamespace(traced=None)) is None


def test_traced_run_reports_the_compile_readers(tmp_path):
    """A traced run of a tiny cell on the CPU: ``serve_batch`` makes its two
    programs, prefill and decode step, anew in every call."""
    root = tiny_copy(tmp_path)
    out = run.run(args("phi3.decode", trace=1), root=root, require_tpu=False)
    m = out["metrics"]
    assert out["correct"]
    assert m["serve_lowerings"] == {"value": 2.0, "unit": "count"}
    calls = out["attempted"] / TINY_TRAFFIC["serve"]["batch"]
    per_call_ms = 1e3 * out["device"]["window_s"] / calls
    assert 0 < m["serve_compile_ms"]["value"] < per_call_ms
