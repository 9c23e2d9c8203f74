"""Device time of one decode step spent outside the model's named scopes, in
ms: the self time of every op that starts inside a run of ``jit_serve_step``
on device 0 and whose name scope holds none of ``/embed/``, ``/norm/``,
``/attn/``, ``/mlp/``, ``/lm_head/``, ``/sample/``, averaged over the
window's runs. What is left is scan plumbing: the slicing and stacking of the
layer-stacked KV cache, layout copies and the loop itself. A program whose
step carries none of the scopes reports nothing."""
from chipbench import trace

STEP = "jit_serve_step"
SCOPES = tuple(f"/{s}/" for s in
               ("embed", "norm", "attn", "mlp", "lm_head", "sample"))


def scoped(scope: str) -> bool:
    s = "/" + scope
    return any(k in s for k in SCOPES)


def read(ctx):
    t = ctx.traced
    if t is None or 0 not in t.devices:
        return None
    dev = t.devices[0]
    runs = [(ts, ts + dur) for ts, dur, name in dev.modules
            if name.split("(", 1)[0] == STEP and trace.in_window(t, ts)]
    if not runs:
        return None
    ops, i = dev.ops, 0
    unscoped_us, any_scoped = 0.0, False
    for start, end in runs:              # one device: runs do not overlap
        while i < len(ops) and ops[i].start < start:
            i += 1
        while i < len(ops) and ops[i].start < end:
            if scoped(ops[i].scope):
                any_scoped = True
            else:
                unscoped_us += ops[i].self_us
            i += 1
    if not any_scoped:
        return None
    return unscoped_us / 1e3 / len(runs)
