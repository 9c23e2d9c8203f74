"""Model operations of every prefill and decode token of the traced calls,
counted from the configuration's shapes, over the traced window times the
chip's bf16 peak, in percent."""
from chipbench import counts


def read(ctx):
    if ctx.traced is None or not ctx.calls or "bf16_flops_per_s" not in ctx.peaks:
        return None
    t = ctx.workload["traffic"]
    m = counts.Decoder.from_config(ctx.config)
    flops = len(ctx.calls) * counts.serve_call_flops(m, t["batch"],
                                                     t["prompt"], t["gen"])
    return 100.0 * flops / (ctx.traced.window_s * ctx.peaks["bf16_flops_per_s"])
