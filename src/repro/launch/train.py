"""Training launcher — the end-to-end driver (data -> pjit train_step ->
checkpoint/resume -> metrics).

On real pods this runs under the production mesh from launch.mesh; on CPU it
uses whatever devices exist. Fault tolerance: atomic keep-k checkpoints +
auto-resume; the data pipeline is a pure function of step, so a restore
replays identical batches.

  PYTHONPATH=src python -m repro.launch.train --arch xlstm-125m --smoke \
      --steps 100 --global-batch 8 --seq-len 128 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import CheckpointManager
from repro.configs import SHAPES, get_config, reduce_for_smoke
from repro.data.synthetic import SyntheticConfig, SyntheticLM
from repro.models import common as cc
from repro.models.registry import get_api
from repro.parallel.sharding import ShardingRules, activation_resolver, param_specs
from repro.training.optimizer import AdamWConfig
from repro.training.train_step import init_train_state, make_train_step
from repro.launch import specs as sp
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh_for


def train_runtime(seq_len: int) -> cc.Runtime:
    """The train step's runtime: XLA attention (the flash kernel has no
    backward pass), in blocks of 256 positions past 512."""
    return cc.Runtime(kernels=False, seq_chunk=256 if seq_len > 512 else 0)


def train_loop(cfg, steps: int, global_batch: int, seq_len: int,
               ckpt_dir: str = "", ckpt_every: int = 50, keep_k: int = 3,
               lr: float = 3e-4, seed: int = 0, log_every: int = 10,
               mesh=None, resume: bool = True, log=print,
               schedule_steps: int = 0):
    api = get_api(cfg)
    # schedule_steps: the PLANNED total (so a run interrupted at `steps` and
    # resumed later sees the identical LR schedule — replay-exact resume)
    sched = schedule_steps or steps
    opt_cfg = AdamWConfig(learning_rate=lr, warmup_steps=min(20, sched // 10),
                          total_steps=sched)

    if mesh is None:
        mesh = make_mesh_for(len(jax.devices()))
    rules = ShardingRules(mesh=mesh, fsdp=mesh.size > 1)

    state = init_train_state(cfg, jax.random.PRNGKey(seed), opt_cfg)
    state_sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                            sp.train_state_specs(rules, state),
                            is_leaf=lambda x: isinstance(x, P))
    state = jax.device_put(state, state_sh)

    step_fn = make_train_step(cfg, opt_cfg, api)
    jitted = jax.jit(step_fn, in_shardings=(state_sh, None),
                     out_shardings=(state_sh, None), donate_argnums=(0,))

    data = SyntheticLM(cfg, SyntheticConfig(global_batch=global_batch,
                                            seq_len=seq_len, seed=seed))
    start_step = 0
    mgr = None
    if ckpt_dir:
        mgr = CheckpointManager(ckpt_dir, keep_k=keep_k)
        if resume:
            latest = mgr.restore_latest(state)
            if latest is not None:
                start_step, state, meta = latest
                log(f"resumed from step {start_step}")

    cc.push_logical_rules(activation_resolver(rules))
    history = []
    try:
        with cc.use_runtime(train_runtime(seq_len)):   # traced in here
            t0 = time.time()
            for step, batch in data.iter(start_step):
                if step >= steps:
                    break
                jb = {k: jnp.asarray(v) for k, v in batch.items()}
                state, metrics = jitted(state, jb)
                if step % log_every == 0 or step == steps - 1:
                    m = {k: float(v) for k, v in metrics.items()}
                    m["step"] = step
                    m["elapsed_s"] = round(time.time() - t0, 1)
                    history.append(m)
                    log(f"step {step:5d} loss {m['loss']:.4f} "
                        f"lr {m['lr']:.2e} gnorm {m['grad_norm']:.2f}")
                if mgr and (step + 1) % ckpt_every == 0:
                    mgr.save(step + 1, state, extra={"data_step": step + 1})
        if mgr:
            mgr.save(steps, state, extra={"data_step": steps})
    finally:
        cc.pop_logical_rules()
    return state, history


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduce_for_smoke(cfg)
        cfg = dataclasses.replace(cfg, remat=False)
    _, history = train_loop(cfg, args.steps, args.global_batch, args.seq_len,
                            ckpt_dir=args.ckpt_dir,
                            ckpt_every=args.ckpt_every, lr=args.lr,
                            seed=args.seed)
    first, last = history[0]["loss"], history[-1]["loss"]
    print(f"loss {first:.4f} -> {last:.4f} "
          f"({'improved' if last < first else 'NOT improved'})")


if __name__ == "__main__":
    main()
