"""Share of its roofline that prefill attention reaches: the least time the
chip needs for causal attention over the prompt (from the configuration's
shapes) over the self time of every op under the ``flash_attention`` name
scope, layout copies and padding included."""
from chipbench import counts, trace


def read(ctx):
    if ctx.traced is None or 0 not in ctx.traced.devices or not ctx.peaks:
        return None
    busy = trace.scope_self_s(ctx.traced, "flash_attention")
    if busy <= 0:
        return None
    t = ctx.workload["traffic"]
    m = counts.Decoder.from_config(ctx.config)
    work = counts.flash_attention(m, t["batch"], t["prompt"])
    need = (work * len(ctx.calls)).seconds(ctx.peaks["bf16_flops_per_s"],
                                           ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * need / busy
