"""Percent of the traced window in which no op runs on the chip (serve)."""
from chipbench import trace


def read(ctx):
    if ctx.traced is None or 0 not in ctx.traced.devices:
        return None
    return trace.idle_share(ctx.traced, 0)
