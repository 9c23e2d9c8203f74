"""Serve driver: closed loop, one client, whole ``serve_batch`` calls.

Each call of the window prefills one batch of prompts and greedily decodes
``gen`` tokens per prompt through ``repro.launch.serve.serve_batch``, the
program's serving entry point. Weights are made on the device from the seed
in one jitted call, in bfloat16, in the program's parameter layout; the
prompts of every call are made before the window. Set-up runs one whole call
on a prompt batch the window never sends, so every program and every shape
of the window is compiled before it opens.

The check: a sample of the finished requests, drawn from the seed, is run
through the plain float32 reference (``references/dense_decoder.py``) over
prompt and served tokens, and the widest gap by which a served token's
reference logit lies below the reference's best at its position is held to
the cell's limit.
"""
from __future__ import annotations

import dataclasses
import gc

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import traffic
from chipbench.references import dense_decoder as ref
from repro.configs import get_config
from repro.configs.base import AttnSpec, LayerSpec, ModelConfig, Segment
from repro.launch.serve import serve_batch

CHECK_STREAM = 3


def model_config(c: dict) -> ModelConfig:
    """The program's configuration of the file's sizes."""
    attn = AttnSpec(n_heads=c["num_attention_heads"],
                    n_kv_heads=c["num_key_value_heads"],
                    head_dim=c["hidden_size"] // c["num_attention_heads"],
                    rope_theta=float(c["rope_theta"]))
    layer = LayerSpec(kind="attn", mlp="dense", attn=attn,
                      d_ff=c["intermediate_size"])
    act = "silu" if c["hidden_act"] == "silu" else "gelu"
    return ModelConfig(name=c["name"], family="dense",
                       d_model=c["hidden_size"], vocab_size=c["vocab_size"],
                       segments=(Segment(count=c["num_hidden_layers"],
                                         layers=(layer,)),),
                       norm=c["norm"], act=act,
                       tie_embeddings=c["tie_word_embeddings"],
                       dtype=c["served_dtype"])


def _norm(spec: ref.Spec, lead: tuple) -> dict:
    p = {"scale": jnp.ones(lead + (spec.d,), jnp.float32)}
    if spec.norm == "layernorm":
        p["bias"] = jnp.zeros(lead + (spec.d,), jnp.float32)
    return p


def program_params(spec: ref.Spec, key) -> dict:
    """All weights in the program's layout: one segment of ``layers``
    stacked blocks. Values are those the reference makes layer by layer."""
    stacked = jax.vmap(lambda l: ref.layer_weights(spec, key, l))(
        jnp.arange(spec.layers))
    lead = (spec.layers,)
    mlp = {k: stacked[k] for k in ("w_up", "w_down", "w_gate") if k in stacked}
    block = {"norm1": _norm(spec, lead),
             "attn": {k: stacked[k] for k in ("wq", "wk", "wv", "wo")},
             "norm2": _norm(spec, lead), "mlp": mlp}
    e = ref.embed_weights(spec, key)
    params = {"embed": e["embed"], "final_norm": _norm(spec, ()),
              "segments": [[block]]}
    if not spec.tied:
        params["lm_head"] = e["lm_head"]
    return params


@dataclasses.dataclass
class State:
    cfg: ModelConfig
    spec: ref.Spec
    key: object
    params: dict
    prompts: list          # device arrays, one batch per pool slot
    prompts_host: list     # the same on the host
    gen: int
    outputs: list = dataclasses.field(default_factory=list)  # (call, tokens)


def setup(ctx, warm: bool = True) -> State:
    c, t = ctx.config, ctx.workload["traffic"]
    cfg = model_config(c)
    if "program_arch" in c:
        prog = get_config(c["program_arch"])
        for f in ("d_model", "vocab_size", "norm", "act", "tie_embeddings"):
            if getattr(prog, f) != getattr(cfg, f):
                raise ValueError(f"{f}: the file says {getattr(cfg, f)!r}, "
                                 f"the program's config {getattr(prog, f)!r}")
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
    return {"requests": b, "tokens": b * state.gen,
            "prefill_s": stats["prefill_s"], "decode_s": stats["decode_s"]}


def release(state: State) -> None:
    state.params = state.prompts = None
    gc.collect()


def end_to_end(ctx) -> dict:
    calls = ctx.calls
    tokens = sum(c.work["tokens"] for c in calls)
    return {"serve_tokens_per_s": tokens / (calls[-1].end - calls[0].start)}


def sample(state: State, n: int, seed: int) -> list:
    """``n`` (call, row) pairs drawn from the seed among the finished
    requests; every request has the longest length."""
    pairs = [(j, r) for j, (_, g) in enumerate(state.outputs)
             for r in range(g.shape[0])]
    rng = traffic.rng_for(seed, CHECK_STREAM)
    pick = rng.choice(len(pairs), size=min(n, len(pairs)), replace=False)
    return [pairs[k] for k in sorted(pick)]


def served(state: State, picks: list) -> tuple[np.ndarray, np.ndarray]:
    """Token rows (prompt and served tokens but the last) and the served
    tokens of the picked requests."""
    rows, chosen = [], []
    for j, r in picks:
        slot, g = state.outputs[j]
        rows.append(np.concatenate([state.prompts_host[slot][r], g[r, :-1]]))
        chosen.append(g[r])
    return np.stack(rows).astype(np.int32), np.stack(chosen)


def _readings(state: State, ctx, control: bool) -> float:
    n = ctx.workload["check"]["requests"]
    prompt = ctx.workload["traffic"]["prompt"]
    tokens, chosen = served(state, sample(state, n, ctx.seed))
    if chosen.min() < 0 or chosen.max() >= state.spec.vocab:
        return float("inf")
    with jax.default_matmul_precision("highest"):
        logits = ref.logits(state.spec, state.key, tokens, prompt - 1)
        if control:
            chosen = ref.logits(state.spec, state.key, tokens, prompt - 1,
                                quantize=True).argmax(-1)
    return ref.widest_gap(logits, chosen)


def check(state: State, ctx) -> list:
    """The widest gap of a served token below the reference's best."""
    from chipbench.run import Check
    return [Check("served_token_gap", _readings(state, ctx, False),
                  ctx.workload["limits"]["served_token_gap"])]


def control(state: State, ctx) -> dict:
    """The same reading for the reference run with float8 matmuls in the
    program's place: at each position of the same requests, the gap of the
    token that float8 puts first."""
    return {"served_token_gap": _readings(state, ctx, True)}
