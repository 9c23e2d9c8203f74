"""Serve driver for a share of a DeepSeek-V2 style MoE decoder: closed loop,
one client, whole ``serve_batch`` calls, as ``drivers/serve.py`` runs them.

The configuration file gives one chip's share of an expert-parallel
deployment: every layer's attention, the experts ``first_local_expert`` ..
``first_local_expert + n_routed_experts - 1`` of the ``router_experts`` the
router scores, the shared experts, and the whole vocabulary. The program is
told the share through ``MoESpec.first_local`` / ``n_local``; a program
without them cannot run the cell, and the driver stops as it is loaded.

Weights are made on the device from the seed in one jitted call, in
bfloat16, in the program's parameter layout, from the same leaves the plain
reference (``references/mla_moe_decoder.py``) makes layer by layer. Each
call's ``work`` carries the MoE counts ``serve_batch`` reports
(``moe_pairs_prefill``, ``moe_pairs_decode``, ``moe_expert_visits_decode``
among them) for the readers.

The check: a sample of the finished requests, drawn from the seed, goes
through the float32 reference over prompt and served tokens. At each served
position the gap by which the served token's reference logit lies below the
reference's best is read, and the share of the served positions whose gap
exceeds the workload's ``gap_tolerance`` is held to the cell's limit. A
share, not the widest gap as in ``drivers/serve.py``: at the stated
precision a rounding of a router's input now and then sends a token to
another expert (about one served position in eight crosses a routing
boundary somewhere in 4 MoE layers), and such a position's gap can be as
large as a fault's, so the widest gap of a correct program and of the
float8 control overlap. The check also prints the held (token, expert)
pairs per decode token that the reference's routing gives on the sampled
requests beside those the program counted over its calls.
"""
from __future__ import annotations

import dataclasses
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import MoESpec

if "n_local" not in {f.name for f in dataclasses.fields(MoESpec)}:
    sys.exit("serve_moe: the program's MoE layer cannot be told which "
             "experts it holds (MoESpec has no n_local)")

from chipbench import traffic  # noqa: E402
from chipbench.drivers.serve import (State, end_to_end, release,  # noqa: E402,F401
                                     sample, served)
from chipbench.references import mla_moe_decoder as ref  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.configs.base import (LayerSpec, MLASpec, ModelConfig,  # noqa: E402
                                Segment)
from repro.launch.serve import serve_batch  # noqa: E402


def model_config(c: dict) -> ModelConfig:
    """The program's configuration of the file's sizes and share."""
    s = ref.Spec.from_config(c)
    mla = MLASpec(n_heads=s.heads, q_lora_rank=s.q_lora,
                  kv_lora_rank=s.kv_lora, qk_nope_dim=s.nope,
                  qk_rope_dim=s.rope, v_head_dim=s.v_dim,
                  rope_theta=s.rope_theta, yarn_factor=s.yarn_factor,
                  yarn_mscale=s.yarn_mscale,
                  yarn_mscale_all_dim=s.yarn_mscale_all_dim,
                  yarn_original_max_pos=s.yarn_original_max,
                  yarn_beta_fast=s.yarn_beta_fast,
                  yarn_beta_slow=s.yarn_beta_slow)
    moe = MoESpec(n_experts=s.router_experts, top_k=s.top_k,
                  d_ff_expert=s.expert_ff, n_shared=s.n_shared,
                  first_local=s.first_local, n_local=s.held,
                  n_group=s.n_group, topk_group=s.topk_group,
                  routed_scale=s.routed_scale, norm_topk=s.norm_topk)
    dense = LayerSpec(kind="mla", mlp="dense", mla=mla, d_ff=s.d_ff)
    sparse = LayerSpec(kind="mla", mlp="moe", mla=mla, moe=moe)
    return ModelConfig(name=c["name"], family="moe", d_model=s.d,
                       vocab_size=s.vocab,
                       segments=(Segment(count=s.first_dense, layers=(dense,)),
                                 Segment(count=s.layers - s.first_dense,
                                         layers=(sparse,))),
                       norm="rmsnorm", act="silu", tie_embeddings=False,
                       dtype=c["served_dtype"])


def _check_program_arch(cfg: ModelConfig, arch: str) -> None:
    """The file's widths and routing are the program's own config's."""
    prog = get_config(arch)
    pairs = [(f, getattr(cfg, f), getattr(prog, f))
             for f in ("d_model", "vocab_size", "norm", "act",
                       "tie_embeddings")]
    mine, theirs = cfg.segments[-1].layers[0], prog.segments[-1].layers[0]
    pairs += [(f"mla.{f.name}", getattr(mine.mla, f.name),
               getattr(theirs.mla, f.name))
              for f in dataclasses.fields(MLASpec)]
    pairs += [(f"moe.{f}", getattr(mine.moe, f), getattr(theirs.moe, f))
              for f in ("n_experts", "top_k", "d_ff_expert", "n_shared",
                        "n_group", "topk_group", "routed_scale", "norm_topk")]
    pairs.append(("d_ff", cfg.segments[0].layers[0].d_ff,
                  prog.segments[0].layers[0].d_ff))
    for name, a, b in pairs:
        if a != b:
            raise ValueError(f"{name}: the file says {a!r}, the program's "
                             f"config {b!r}")


def _block(s: ref.Spec, w: dict, lead: tuple, moe: bool) -> dict:
    def ones(n):
        return {"scale": jnp.ones(lead + (n,), jnp.float32)}

    mla = {k: w[k] for k in ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo")}
    mla.update(q_norm=ones(s.q_lora), kv_norm=ones(s.kv_lora))
    layer = {"norm1": ones(s.d), "mla": mla, "norm2": ones(s.d)}
    if not moe:
        layer["mlp"] = {k: w[k] for k in ("w_up", "w_gate", "w_down")}
        return layer
    layer["moe"] = {"router": w["router"].astype(jnp.float32),
                    "w_up": w["e_up"], "w_gate": w["e_gate"],
                    "w_down": w["e_down"]}
    if s.n_shared:
        layer["moe"]["shared"] = {"w_up": w["s_up"], "w_gate": w["s_gate"],
                                  "w_down": w["s_down"]}
    return layer


def program_params(s: ref.Spec, key) -> dict:
    """All weights in the program's layout: a segment of the leading dense
    layers, then one of the MoE layers, each stacked where it has more than
    one. Values are those the reference makes layer by layer."""
    segments = []
    for first, count, moe in ((0, s.first_dense, False),
                              (s.first_dense, s.layers - s.first_dense, True)):
        if count == 1:
            segments.append([_block(s, ref.layer_weights(s, key, first, moe),
                                    (), moe)])
        else:
            w = jax.vmap(lambda l, _m=moe: ref.layer_weights(s, key, l, _m))(
                jnp.arange(first, first + count))
            segments.append([_block(s, w, (count,), moe)])
    e = ref.embed_weights(s, key)
    return {"embed": e["embed"], "lm_head": e["lm_head"],
            "final_norm": {"scale": jnp.ones((s.d,), jnp.float32)},
            "segments": segments}


def setup(ctx, warm: bool = True) -> State:
    c, t = ctx.config, ctx.workload["traffic"]
    cfg = model_config(c)
    if "program_arch" in c:
        _check_program_arch(cfg, c["program_arch"])
    spec = ref.Spec.from_config(c)
    key = traffic.jax_key(ctx.seed)
    params = jax.jit(program_params, static_argnums=0)(spec, key)
    host = [traffic.prompts(spec.vocab, t["batch"], t["prompt"], ctx.seed, i)
            for i in range(t["pool"] + 1)]
    dev = [{"tokens": jnp.asarray(p)} for p in host]
    jax.block_until_ready((params, dev))
    state = State(cfg, spec, key, params, dev[:-1], host[:-1], t["gen"])
    if warm:
        serve_batch(cfg, params, dev[-1], t["gen"], log=lambda *a: None)
    return state


def call(state: State, i: int) -> dict:
    slot = i % len(state.prompts)
    gen, stats = serve_batch(state.cfg, state.params, state.prompts[slot],
                             state.gen, log=lambda *a: None)
    state.outputs.append((slot, gen))
    b = gen.shape[0]
    work = {"requests": b, "tokens": b * state.gen,
            "prefill_s": stats["prefill_s"], "decode_s": stats["decode_s"]}
    work.update({k: v for k, v in stats.items() if k.startswith("moe_")})
    return work


def readings(state: State, ctx, quantize: bool = False) -> dict:
    """Per served position of the sampled requests, the gap of the served
    token (or, with ``quantize``, of the float8 reference's own best) below
    the reference's best; and the held pairs per decode token that the
    reference routes."""
    n = ctx.workload["check"]["requests"]
    prompt = ctx.workload["traffic"]["prompt"]
    tokens, chosen = served(state, sample(state, n, ctx.seed))
    if chosen.min() < 0 or chosen.max() >= state.spec.vocab:
        return {"gaps": np.full(chosen.shape, np.inf), "pairs": np.nan}
    with jax.default_matmul_precision("highest"):
        logits, held = ref.forward(state.spec, state.key, tokens, prompt - 1)
        if quantize:
            chosen = ref.logits(state.spec, state.key, tokens, prompt - 1,
                                quantize=True).argmax(-1)
    got = np.take_along_axis(logits, chosen[..., None], -1)[..., 0]
    return {"gaps": logits.max(-1) - got,
            "pairs": float(held[:, prompt:].mean())}


def miss_share(r: dict, ctx) -> float:
    """Share of the served positions whose gap exceeds the tolerance."""
    tol = ctx.workload["check"]["gap_tolerance"]
    gaps = r["gaps"]
    t = ctx.workload["traffic"]
    done = [c.work for c in ctx.calls if "moe_pairs_decode" in c.work]
    program = (sum(w["moe_pairs_decode"] for w in done)
               / max(len(done) * t["batch"] * (t["gen"] - 1), 1))
    print(f"serve_moe: {int((gaps > tol).sum())} of {gaps.size} served "
          f"positions more than {tol} below the best; widest gap "
          f"{float(gaps.max())!r}; held pairs per decode token: reference "
          f"{r['pairs']!r} (sampled requests), program {program!r} "
          f"({len(done)} calls)", file=sys.stderr)
    return float(np.mean(gaps > tol))


def check(state: State, ctx) -> list:
    """The share of served positions whose token lies more than the
    tolerance below the reference's best."""
    from chipbench.run import Check
    return [Check("served_token_miss_share",
                  miss_share(readings(state, ctx), ctx),
                  ctx.workload["limits"]["served_token_miss_share"])]


def control(state: State, ctx) -> dict:
    """The same reading for the reference run with float8 matmuls in the
    program's place."""
    return {"served_token_miss_share": miss_share(
        readings(state, ctx, True), ctx)}
