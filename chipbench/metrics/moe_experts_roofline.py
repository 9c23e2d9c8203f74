"""Share of its roofline that the routed experts reach in decode: the least
time the chip needs for every decode pair through its expert and every
visited expert's weights read once (from the program's ``moe_pairs_decode``
and ``moe_expert_visits_decode``) over the self time of the decode step's
expert layer, the ops that start inside a run of ``jit_serve_step`` and

  * lie under the ``moe_experts`` name scope,
  * are the TPU's grouped matmul itself (``ragged-dot...``, a custom call
    that carries no name scope), or
  * are the layer scan's copies of the layer's three expert arrays out of
    the stacked weights (name scope ``.../while/body/squeeze``), which feed
    the grouped matmul: a custom call cannot read a slice in place. The
    attention weights' slices that XLA copies carry the scope
    ``.../while/body/dynamic_slice`` and are left to ``decode_unscoped_ms``.
"""
from chipbench import counts_moe, trace

STEP = "jit_serve_step"
SCOPE, KERNEL, FEED = "/moe_experts/", "ragged-dot", "/while/body/squeeze"
NEEDS = ("moe_pairs_decode", "moe_expert_visits_decode")


def _expert_op(op) -> bool:
    return (SCOPE in op.scope or op.name.lstrip("%").startswith(KERNEL)
            or op.scope.rstrip(":").endswith(FEED))


def read(ctx):
    t = ctx.traced
    if (t is None or 0 not in t.devices or not ctx.peaks
            or not all(k in c.work for c in ctx.calls for k in NEEDS)):
        return None
    runs = [(ts, ts + dur) for ts, dur, name in t.devices[0].modules
            if name.split("(", 1)[0] == STEP and trace.in_window(t, ts)]
    busy = sum(o.self_us for o in t.devices[0].ops
               if _expert_op(o) and any(a <= o.start < b for a, b in runs))
    if busy <= 0:
        return None
    m = counts_moe.MlaMoe.from_config(ctx.config)
    work = counts_moe.decode_experts(
        m, sum(c.work["moe_pairs_decode"] for c in ctx.calls),
        sum(c.work["moe_expert_visits_decode"] for c in ctx.calls))
    need = work.seconds(ctx.peaks["bf16_flops_per_s"],
                        ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * need / (busy / 1e6)
