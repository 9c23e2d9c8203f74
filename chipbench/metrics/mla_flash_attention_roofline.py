"""Share of its roofline that MLA prefill attention reaches: the least time
the chip needs for causal MLA over the prompt (from the configuration's
shapes: scores over qk_nope + qk_rope, values of v_head_dim, the shared
k_rope read once) over the self time of every op under the
``flash_attention`` name scope, layout copies and padding included."""
from chipbench import counts_moe, trace


def read(ctx):
    if ctx.traced is None or 0 not in ctx.traced.devices or not ctx.peaks:
        return None
    busy = trace.scope_self_s(ctx.traced, "flash_attention")
    if busy <= 0:
        return None
    t = ctx.workload["traffic"]
    m = counts_moe.MlaMoe.from_config(ctx.config)
    work = counts_moe.mla_flash_attention(m, t["batch"], t["prompt"])
    need = (work * len(ctx.calls)).seconds(ctx.peaks["bf16_flops_per_s"],
                                           ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * need / busy
