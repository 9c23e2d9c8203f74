"""Serving launcher — batched prefill + greedy decode over the registry API.

  PYTHONPATH=src python -m repro.launch.serve --arch gemma3-1b --smoke \
      --batch 4 --prompt-len 32 --gen 16
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs import get_config, reduce_for_smoke
from repro.data.synthetic import SyntheticConfig, make_batch
from repro.launch.compile_cache import enable_compile_cache
from repro.models.decoder_lm import has_moe
from repro.models.registry import get_api
from repro.training.train_step import make_decode_step, make_prefill


def serve_batch(cfg, params, batch: dict, gen_tokens: int, log=print):
    """Prefill the prompt batch, then greedy-decode gen_tokens. Returns
    (generated (B, gen), stats dict).

    The decode step donates its KV-cache argument, so every step writes the
    new token into the prefill-time allocation instead of allocating a fresh
    cache pytree per token (the caches dominate serving memory:
    B x max_len x layers). ``stats`` is machine-readable so harnesses
    (benchmarks/serve_bench.py) can calibrate simulated replica costs from a
    real measured decode rate instead of parsing log lines. ``decode_s`` is
    ``dispatch_s``, the host's loop over the decode steps, plus ``drain_s``,
    the wait for the device to finish them. A decoder LM with MoE layers
    adds their counts (``models.mlp.moe_dropless``), each as
    ``<name>_prefill`` and ``<name>_decode``: summed over layers by the
    step programs, over decode steps on the device, and fetched once, at
    gather.

    Four profiler annotations, ``launch.serve.prefill``,
    ``launch.serve.decode.dispatch``, ``launch.serve.decode.drain`` and
    ``launch.serve.gather``, mark the phases in a profiler's trace on the
    device's clock; with no profiler collecting they cost a check each."""
    if jax.default_backend() == "tpu":
        from repro.models import common as cc
        cc.RUNTIME["use_flash"] = True   # Pallas flash/decode kernels
    api = get_api(cfg)
    counted = cfg.family not in ("audio", "vlm") and has_moe(cfg)
    prefill_fn = make_prefill(cfg, api, counts=counted)
    # donate the cache pytree (argnum 3): decode_step's dynamic-update-slice
    # then updates the caches in place, reusing the allocation across steps
    decode_fn = jax.jit(make_decode_step(cfg, api), donate_argnums=(3,))
    b, s = batch["tokens"].shape
    extra = cfg.n_patches if cfg.family == "vlm" else 0
    max_len = extra + s + gen_tokens

    t0 = time.perf_counter()
    with TraceAnnotation("launch.serve.prefill"):
        last_logits, caches, *counts = jax.jit(
            prefill_fn, static_argnums=(2,))(params, batch, max_len)
        token = jnp.argmax(last_logits[:, -1], axis=-1).astype(
            jnp.int32)[:, None]
        jax.block_until_ready(token)
    t_prefill = time.perf_counter() - t0

    out = [token]
    totals = [jax.tree.map(jnp.zeros_like, c) for c in counts]
    t0 = time.perf_counter()
    with TraceAnnotation("launch.serve.decode.dispatch"):
        for i in range(gen_tokens - 1):
            pos = jnp.int32(extra + s + i)
            token, caches, *totals = decode_fn(params, token, pos, caches,
                                               *totals)
            out.append(token)
    t1 = time.perf_counter()
    with TraceAnnotation("launch.serve.decode.drain"):
        jax.block_until_ready(token)
    t2 = time.perf_counter()
    t_dispatch, t_drain = t1 - t0, t2 - t1
    t_decode = t_dispatch + t_drain
    with TraceAnnotation("launch.serve.gather"):
        gen = np.asarray(jnp.concatenate(out, axis=1))
        counts = jax.device_get({"prefill": counts, "decode": totals})
    decode_steps = gen_tokens - 1
    stats = {
        "batch": b,
        "prompt_tokens": s,
        "gen_tokens": gen_tokens,
        "prefill_s": t_prefill,
        "prefill_tokens": b * s,
        "prefill_tokens_per_s": b * s / max(t_prefill, 1e-9),
        "decode_s": t_decode,
        "dispatch_s": t_dispatch,
        "drain_s": t_drain,
        "decode_steps": decode_steps,
        "decode_tokens": b * decode_steps,
        "tokens_per_s": b * decode_steps / max(t_decode, 1e-9),
        "decode_s_per_token": (t_decode / max(b * decode_steps, 1)),
        "backend": jax.default_backend(),
    }
    stats.update({f"{k}_{phase}": int(v) for phase, c in counts.items()
                  for k, v in (c[0] if c else {}).items()})
    log(f"prefill {s} toks x{b}: {t_prefill:.2f}s; "
        f"decode {decode_steps} steps: {t_decode:.2f}s "
        f"({stats['tokens_per_s']:.1f} tok/s)")
    return gen, stats


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduce_for_smoke(cfg)
        cfg = dataclasses.replace(cfg, remat=False)
    api = get_api(cfg)
    params = api.init_params(cfg, jax.random.PRNGKey(args.seed))
    batch = {k: jnp.asarray(v) for k, v in make_batch(
        cfg, SyntheticConfig(global_batch=args.batch,
                             seq_len=args.prompt_len,
                             seed=args.seed), 0).items()}
    gen, stats = serve_batch(cfg, params, batch, args.gen)
    print(f"generated shape {gen.shape}; sample row: {gen[0][:8].tolist()}")
    print("stats: " + " ".join(f"{k}={v}" for k, v in stats.items()))


if __name__ == "__main__":
    main()
