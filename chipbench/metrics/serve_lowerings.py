"""Programs the serve entry point lowers per call: the number of JAX's own
``lower_sharding_computation`` host spans (one for each jitted function
traced and lowered to a new program) that start inside the traced window,
over the number of calls. A call that reuses the programs an earlier call
made reads 0."""
from chipbench import trace

LOWER = "lower_sharding_computation"


def read(ctx):
    t = ctx.traced
    if t is None or not t.calls:
        return None
    n = sum(1 for ts, _, name, _ in t.host
            if name == LOWER and trace.in_window(t, ts))
    return n / len(t.calls)
