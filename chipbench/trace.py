"""Reduce a profiler trace to the numbers the per-layer metrics read.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.trace.json.gz``
beside the ``.xplane.pb``. In it every device is a process named
``/device:TPU:<n>`` with a thread ``XLA Ops`` (one event per executed HLO op,
nested: a ``while`` holds its body's ops, and ``args.tf_op`` gives the op's
name scope, e.g. ``jit(serve_step)/while/body/closed_call/
jit(decode_attention)/jit(_pad)/pad:``) and a thread ``XLA Modules`` (one
event per executed program, named ``jit_<fn>(<fingerprint>)``). Host threads
are in the process ``/host:CPU``; the benchmark's own spans are there, named
``CALL_SPAN``. Times are microseconds on one clock.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import gzip
import json
import os

CALL_SPAN = "chipbench.call"


@dataclasses.dataclass
class Op:
    start: float       # us
    dur: float         # us
    name: str
    scope: str         # tf_op
    category: str      # hlo_category
    self_us: float = 0.0


@dataclasses.dataclass
class Device:
    ops: list          # [Op] of the ``XLA Ops`` line, sorted by start
    modules: list      # [(start, dur, name)] of the ``XLA Modules`` line


@dataclasses.dataclass
class Trace:
    devices: dict              # device index -> Device
    host: list                 # [(start, dur, name, tid)]
    calls: list                # [(start, end)] of CALL_SPAN host spans
    python_tid: int | None = None

    @property
    def window(self) -> tuple[float, float]:
        return self.calls[0][0], self.calls[-1][1]

    @property
    def window_s(self) -> float:
        a, b = self.window
        return (b - a) / 1e6


def find_trace_file(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.trace.json.gz"),
                      recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"{len(files)} trace files under {trace_dir}")
    return files[0]


def _self_times(ops: list) -> None:
    """Self time of each op: its duration less that of the ops nested
    directly inside it on the same line (a ``while`` and its body)."""
    stack: list = []
    for op in ops:
        op.self_us = op.dur
        end = op.start + op.dur
        while stack and end > stack[-1].start + stack[-1].dur:
            stack.pop()
        if stack:
            stack[-1].self_us -= op.dur
        stack.append(op)


def parse(events: list) -> Trace:
    """Build a ``Trace`` from the events of a Chrome-format trace."""
    proc, thread = {}, {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            proc[e["pid"]] = e["args"]["name"]
        elif e.get("ph") == "M" and e.get("name") == "thread_name":
            thread[(e["pid"], e["tid"])] = e["args"]["name"]
    dev_pid = {pid: int(n.rsplit(":", 1)[1]) for pid, n in proc.items()
               if n.startswith("/device:TPU:") and n.rsplit(":", 1)[1].isdigit()}
    host_pids = {pid for pid, n in proc.items() if n.startswith("/host:")}
    devices = {i: Device([], []) for i in dev_pid.values()}
    host, calls = [], []
    python_tid = None
    for (pid, tid), n in thread.items():
        if pid in host_pids and n == "python":
            python_tid = tid
    for e in events:
        if e.get("ph") != "X":
            continue
        pid, tid = e.get("pid"), e.get("tid")
        ts, dur = float(e["ts"]), float(e.get("dur", 0.0))
        if pid in dev_pid:
            line = thread.get((pid, tid))
            d = devices[dev_pid[pid]]
            if line == "XLA Ops":
                a = e.get("args", {})
                d.ops.append(Op(ts, dur, e["name"], a.get("tf_op", ""),
                                a.get("hlo_category", "")))
            elif line == "XLA Modules":
                d.modules.append((ts, dur, e["name"]))
        elif pid in host_pids:
            host.append((ts, dur, e["name"], tid))
            if e["name"] == CALL_SPAN:
                calls.append((ts, ts + dur))
    for d in devices.values():
        d.ops.sort(key=lambda o: (o.start, -o.dur))
        d.modules.sort()
        _self_times(d.ops)
    calls.sort()
    host.sort()
    return Trace(devices, host, calls, python_tid)


def load(trace_dir: str) -> Trace:
    with gzip.open(find_trace_file(trace_dir), "rt") as f:
        data = json.load(f)
    events = data["traceEvents"] if isinstance(data, dict) else data
    return parse(events)


# ---------------------------------------------------------------------------
# Interval arithmetic
# ---------------------------------------------------------------------------
def union(intervals, lo: float, hi: float) -> list:
    """Merged [start, end) intervals, clipped to [lo, hi)."""
    out: list = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def covered(intervals) -> float:
    return sum(e - s for s, e in intervals)


def busy_intervals(trace: Trace, device: int) -> list:
    lo, hi = trace.window
    return union(((o.start, o.start + o.dur) for o in
                  trace.devices[device].ops), lo, hi)


def busy_s(trace: Trace, device: int) -> float:
    return covered(busy_intervals(trace, device)) / 1e6


def mean_busy_s(trace: Trace) -> float:
    """Busy seconds averaged over the devices that ran an op in the
    window: the chips the cell used."""
    used = [b for b in (busy_s(trace, d) for d in trace.devices) if b > 0]
    return sum(used) / len(used) if used else 0.0


def idle_share(trace: Trace, device: int = 0) -> float:
    """Percent of the traced window in which no op runs on ``device``."""
    return 100.0 * (1.0 - busy_s(trace, device) / trace.window_s)


def in_window(trace: Trace, start: float) -> bool:
    lo, hi = trace.window
    return lo <= start < hi


def scope_self_s(trace: Trace, scope: str, device: int = 0) -> float:
    """Self time of every op in the window whose name scope contains
    ``jit(<scope>)`` or ``/<scope>/``."""
    keys = (f"jit({scope})", f"/{scope}/")
    return sum(o.self_us for o in trace.devices[device].ops
               if in_window(trace, o.start)
               and any(k in o.scope for k in keys)) / 1e6


def module_runs(trace: Trace, prefix: str, device: int = 0) -> list:
    """Durations (s) of the window's runs of programs named
    ``<prefix>(...)``."""
    return [dur / 1e6 for ts, dur, name in trace.devices[device].modules
            if name.split("(", 1)[0] == prefix and in_window(trace, ts)]


# ---------------------------------------------------------------------------
# Breakdown for the result line
# ---------------------------------------------------------------------------
def _op_label(o: Op) -> str:
    if o.scope:
        return o.scope.rstrip(":")
    return o.name.split(" ", 1)[0].lstrip("%")


def top_device_ops(trace: Trace, n: int = 10, device: int = 0) -> list:
    """The ``n`` op names (by name scope) with the most self time in the
    window, as [name, seconds]."""
    acc: collections.Counter = collections.Counter()
    for o in trace.devices[device].ops:
        if in_window(trace, o.start) and o.self_us > 0:
            acc[_op_label(o)] += o.self_us / 1e6
    return [[k, v] for k, v in acc.most_common(n)]


def idle_gaps(trace: Trace, n: int = 10, device: int = 0) -> list:
    """Idle time of ``device`` in the window, summed by the innermost host
    span on the Python thread that covers each gap's middle, as
    [name, seconds], the ``n`` largest."""
    lo, hi = trace.window
    busy = busy_intervals(trace, device)
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    # spans of one thread nest, so a sweep in time order keeps the spans
    # open at each gap's middle on a stack, innermost on top
    spans = [h for h in trace.host if trace.python_tid is None
             or h[3] == trace.python_tid]
    acc: collections.Counter = collections.Counter()
    stack: list = []
    i = 0
    for s, e in gaps:
        mid = (s + e) / 2
        while i < len(spans) and spans[i][0] <= mid:
            while stack and stack[-1][0] + stack[-1][1] < spans[i][0]:
                stack.pop()
            stack.append(spans[i])
            i += 1
        while stack and stack[-1][0] + stack[-1][1] < mid:
            stack.pop()
        acc[stack[-1][2] if stack else "no host span"] += (e - s) / 1e6
    return [[k, v] for k, v in acc.most_common(n)]
