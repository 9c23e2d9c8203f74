"""The reduction from a profiler trace to per-layer numbers."""
from pathlib import Path

import pytest

from chipbench import trace

DATA = Path(__file__).parent / "data"
DEV, HOST, OPS, MODS, PY = 3, 7, 3, 2, 9


def meta():
    return [
        {"ph": "M", "pid": DEV, "name": "process_name",
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "pid": DEV, "tid": OPS, "name": "thread_name",
         "args": {"name": "XLA Ops"}},
        {"ph": "M", "pid": DEV, "tid": MODS, "name": "thread_name",
         "args": {"name": "XLA Modules"}},
        {"ph": "M", "pid": HOST, "name": "process_name",
         "args": {"name": "/host:CPU"}},
        {"ph": "M", "pid": HOST, "tid": PY, "name": "thread_name",
         "args": {"name": "python"}},
    ]


def op(ts, dur, name, scope="", cat=""):
    return {"ph": "X", "pid": DEV, "tid": OPS, "ts": ts, "dur": dur,
            "name": name, "args": {"tf_op": scope, "hlo_category": cat}}


def host(ts, dur, name):
    return {"ph": "X", "pid": HOST, "tid": PY, "ts": ts, "dur": dur,
            "name": name}


@pytest.fixture
def small():
    """A 50 us window: a while op [0, 10) holding a fusion [2, 5), a pad
    under decode_attention's scope [20, 30), an all-gather [25, 40); one
    program run; host spans for the call and for [12, 18)."""
    return trace.parse(meta() + [
        op(0, 10, "while.1", "jit(f)/while", "control flow"),
        op(2, 3, "fusion.2", "jit(f)/while/body/dot"),
        op(20, 10, "pad.3", "jit(f)/closed_call/jit(decode_attention)/pad"),
        op(25, 15, "all-gather.4", "jit(f)/all_gather", "collective"),
        {"ph": "X", "pid": DEV, "tid": MODS, "ts": 0, "dur": 40,
         "name": "jit_f(123)"},
        host(0, 50, trace.CALL_SPAN),
        host(12, 6, "inner"),
    ])


def test_busy_union_and_idle_share(small):
    assert small.window == (0, 50)
    assert trace.busy_intervals(small, 0) == [[0, 10], [20, 40]]
    assert trace.busy_s(small, 0) == pytest.approx(30e-6)
    assert trace.idle_share(small, 0) == pytest.approx(40.0)


def test_self_time_and_scope(small):
    ops = {o.name: o for o in small.devices[0].ops}
    assert ops["while.1"].self_us == 7 and ops["fusion.2"].self_us == 3
    assert trace.scope_self_s(small, "decode_attention") == pytest.approx(10e-6)
    assert trace.scope_self_s(small, "flash_attention") == 0
    assert trace.module_runs(small, "jit_f") == [pytest.approx(40e-6)]


def test_breakdown(small):
    gaps = dict(trace.idle_gaps(small))
    assert gaps == {"inner": pytest.approx(10e-6),
                    trace.CALL_SPAN: pytest.approx(10e-6)}
    top = dict(trace.top_device_ops(small))
    assert top["jit(f)/all_gather"] == pytest.approx(15e-6)
    assert top["jit(f)/while"] == pytest.approx(7e-6)


def test_recorded_decode_steps():
    """Two phi3-mini decode steps recorded on a TPU v5e: the decode kernel,
    its pads and copies sit under ``jit(decode_attention)``."""
    t = trace.load(str(DATA))
    runs = trace.module_runs(t, "jit_serve_step")
    assert len(runs) >= 2 and all(0.05 < r < 0.2 for r in runs)
    kernel = [o for o in t.devices[0].ops if "decode_attention" in o.name]
    assert kernel and all("jit(decode_attention)" in o.scope for o in kernel)
    scoped = trace.scope_self_s(t, "decode_attention")
    assert sum(o.self_us for o in kernel) / 1e6 < scoped < sum(runs)
    assert 0 < trace.idle_share(t) < 100
    assert trace.mean_busy_s(t) <= t.window_s
