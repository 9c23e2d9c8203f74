"""Host time per call spent making programs before their first launch, in
ms. Around each of JAX's ``lower_sharding_computation`` host spans in the
traced window, the outermost ``PjitFunction(...)`` span on the same thread
is the jitted call that made a program; its interval runs from that span's
start to the first ``ExecuteReplicated.__call__`` inside it, the program's
first launch (or to the span's end if there is none). That covers tracing,
lowering, and the backend compile or the load from the persistent cache.
The union of these intervals over the window, over the number of calls; 0
when no call makes a program."""
from chipbench import trace

LOWER = "lower_sharding_computation"
JIT = "PjitFunction("
LAUNCH = "ExecuteReplicated.__call__"


def read(ctx):
    t = ctx.traced
    if t is None or not t.calls:
        return None
    lo, hi = t.window
    made = []
    for ts, dur, name, tid in t.host:
        if name != LOWER or not lo <= ts < hi:
            continue
        end = ts + dur
        outer = [(s, s + d) for s, d, n, i in t.host
                 if i == tid and n.startswith(JIT) and s <= ts and s + d >= end]
        start, stop = min(outer, key=lambda iv: (iv[0], -iv[1]),
                          default=(ts, end))
        launch = min((s for s, _, n, i in t.host
                      if i == tid and n == LAUNCH and end <= s < stop),
                     default=stop)
        made.append((start, launch))
    return trace.covered(trace.union(made, lo, hi)) / 1e3 / len(t.calls)
