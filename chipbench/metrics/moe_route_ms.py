"""Device time of one decode step spent routing tokens to experts and
combining their results, in ms: the self time of the ops of the decode step
(``jit(serve_step)``) under the ``moe_route`` or ``moe_combine`` name scopes,
over the window's runs of ``jit_serve_step``."""
from chipbench import trace

STEP = "jit(serve_step)"
SCOPES = ("/moe_route/", "/moe_combine/")


def read(ctx):
    t = ctx.traced
    if t is None or 0 not in t.devices:
        return None
    runs = trace.module_runs(t, "jit_serve_step")
    us = sum(o.self_us for o in t.devices[0].ops
             if trace.in_window(t, o.start) and STEP in o.scope
             and any(s in o.scope for s in SCOPES))
    if not runs or us <= 0:
        return None
    return us / 1e3 / len(runs)
