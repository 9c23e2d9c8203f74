"""Share of its roofline that absorbed MLA decode attention reaches: the
least time the chip needs to score the latent rows of the filled positions
with every head and sum their compressed part (the rows read once) over the
self time of every op under the ``decode_attention`` name scope, layout
copies and padding included."""
from chipbench import counts_moe, trace


def read(ctx):
    if ctx.traced is None or 0 not in ctx.traced.devices or not ctx.peaks:
        return None
    busy = trace.scope_self_s(ctx.traced, "decode_attention")
    if busy <= 0:
        return None
    t = ctx.workload["traffic"]
    m = counts_moe.MlaMoe.from_config(ctx.config)
    work = counts_moe.absorbed_decode_attention_call(m, t["batch"],
                                                     t["prompt"], t["gen"])
    need = (work * len(ctx.calls)).seconds(ctx.peaks["bf16_flops_per_s"],
                                           ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * need / busy
