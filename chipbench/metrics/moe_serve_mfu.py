"""Model operations of every prefill and decode token of the traced calls of
an MLA + MoE share, counted from the configuration's shapes and the
program's MoE counters (routed experts per pair computed), over the traced
window times the chip's bf16 peak, in percent."""
from chipbench import counts_moe

NEEDS = ("moe_pairs_prefill", "moe_pairs_decode")


def read(ctx):
    if (ctx.traced is None or not ctx.calls
            or "bf16_flops_per_s" not in ctx.peaks
            or not all(k in c.work for c in ctx.calls for k in NEEDS)):
        return None
    t = ctx.workload["traffic"]
    m = counts_moe.MlaMoe.from_config(ctx.config)
    flops = sum(counts_moe.serve_call_flops(
        m, t["batch"], t["prompt"], t["gen"], c.work["moe_pairs_prefill"],
        c.work["moe_pairs_decode"]) for c in ctx.calls)
    return 100.0 * flops / (ctx.traced.window_s * ctx.peaks["bf16_flops_per_s"])
