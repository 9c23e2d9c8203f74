"""Device time of one run of the decode step program (``jit_serve_step``,
built by ``training.train_step.make_decode_step``), in ms, averaged over the
traced window."""
from chipbench import trace


def read(ctx):
    if ctx.traced is None or 0 not in ctx.traced.devices:
        return None
    runs = trace.module_runs(ctx.traced, "jit_serve_step")
    return 1e3 * sum(runs) / len(runs) if runs else None
