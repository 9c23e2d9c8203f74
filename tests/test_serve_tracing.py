"""What the serving path marks for a profiler: the four host annotations
inside ``serve_batch``, the split of its decode time, and the named scopes of
the step programs."""
import dataclasses
import glob
import gzip
import json
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.configs import get_config, reduce_for_smoke
from repro.launch.serve import serve_batch
from repro.models.registry import get_api
from repro.training.train_step import make_decode_step, make_prefill

SPANS = ["launch.serve.prefill", "launch.serve.decode.dispatch",
         "launch.serve.decode.drain", "launch.serve.gather"]
SCOPES = ("embed", "norm", "attn", "mlp", "lm_head", "sample")
CALL = "test.call"


def _smoke(arch):
    return dataclasses.replace(reduce_for_smoke(get_config(arch)),
                               remat=False)


@pytest.fixture(scope="module")
def phi3():
    cfg = _smoke("phi3-mini-3.8b")
    params = get_api(cfg).init_params(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": jnp.ones((2, 8), jnp.int32)}
    return cfg, params, batch


def _profiled(fn, calls, out):
    """Run ``fn`` ``calls`` times under a profiler, each call inside a host
    annotation ``CALL``; the host spans of the trace as (ts, end, name),
    sorted, and what each call returned."""
    jax.profiler.start_trace(str(out), create_perfetto_trace=True)
    try:
        got = []
        for _ in range(calls):
            with jax.profiler.TraceAnnotation(CALL):
                got.append(fn())
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(out, "**", "*.trace.json.gz"),
                      recursive=True)
    with gzip.open(path, "rt") as f:
        events = json.load(f)["traceEvents"]
    names = set(SPANS) | {CALL}
    spans = sorted((float(e["ts"]), float(e["ts"]) + float(e["dur"]),
                    e["name"]) for e in events
                   if e.get("ph") == "X" and e.get("name") in names)
    return spans, got


def _serve(phi3):
    cfg, params, batch = phi3
    return lambda: serve_batch(cfg, params, batch, 4, log=lambda *a: None)


def test_serve_batch_makes_no_recorder_call_when_off(phi3):
    before = obs.NULL.calls
    assert obs.current() is obs.NULL
    gen, stats = _serve(phi3)()
    assert gen.shape == (2, 4)
    assert obs.NULL.calls == before
    null = obs.NullRecorder()
    with obs.recording(null):
        _serve(phi3)()
    assert null.calls == 0


def test_serve_batch_spans_in_order_inside_the_call(phi3, tmp_path):
    """Each call's four spans follow one another, in order, inside it."""
    spans, _ = _profiled(_serve(phi3), 2, tmp_path)
    calls = [(a, b) for a, b, n in spans if n == CALL]
    assert len(calls) == 2
    for lo, hi in calls:
        inside = [(a, b, n) for a, b, n in spans
                  if n != CALL and lo <= a and b <= hi]
        assert [n for _, _, n in inside] == SPANS
        for (_, end, _), (start, _, _) in zip(inside, inside[1:]):
            assert end <= start
    assert sum(n != CALL for _, _, n in spans) == 2 * len(SPANS)


def test_serve_batch_splits_decode_into_dispatch_and_drain(phi3, tmp_path):
    """``decode_s`` is ``dispatch_s`` plus ``drain_s``; each is read on
    either side of its span, so it holds the span's time."""
    spans, got = _profiled(_serve(phi3), 1, tmp_path)
    (_, stats), = got
    assert stats["decode_s"] == stats["dispatch_s"] + stats["drain_s"]
    dur = {n: b - a for a, b, n in spans}
    for span, key in (("launch.serve.decode.dispatch", "dispatch_s"),
                      ("launch.serve.decode.drain", "drain_s")):
        assert 0 < dur[span] <= stats[key] * 1e6 + 1


def _op_names(lowered) -> list:
    return re.findall(r'op_name="([^"]*)"', lowered.compile().as_text())


def _has(names, scope):
    return any(f"/{scope}/" in n for n in names)


@pytest.mark.parametrize("arch", ["phi3-mini-3.8b", "olmoe-1b-7b"])
def test_step_programs_carry_the_scopes(arch):
    cfg = _smoke(arch)
    api = get_api(cfg)
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": jnp.ones((2, 8), jnp.int32)}
    prefill = jax.jit(make_prefill(cfg, api), static_argnums=(2,))
    _, caches = prefill(params, batch, 12)
    step = jax.jit(make_decode_step(cfg, api))
    dec = _op_names(step.lower(params, batch["tokens"][:, :1], jnp.int32(8),
                               caches))
    pre = _op_names(prefill.lower(params, batch, 12))
    assert all(_has(dec, s) for s in SCOPES)
    assert all(_has(pre, s) for s in SCOPES if s != "sample")
    assert not _has(pre, "sample")
    # the scan's own slicing of the layer-stacked cache carries no scope
    assert "jit(serve_step)/while/body/dynamic_slice" in dec
    if cfg.segments[0].layers[0].mlp == "moe":
        assert any("/mlp/" in n and "top_k" in n for n in dec)
