"""GPipe pipeline parallelism with shard_map + collective_permute.

The jax-native mapping of the paper's GPipe substrate (SS2.1): stages live on
a mesh axis; microbatches march through the stage chain with
``jax.lax.ppermute`` handing activations to the next stage each tick
(fwd: perm i->i+1). ``jax.grad`` differentiates straight through — the
transpose of ppermute is ppermute with the inverse permutation, which IS the
backward activation-gradient hop, so one definition serves fwd+bwd.

Schedule (classic GPipe): T = n_micro + n_stages - 1 ticks; stage s works on
microbatch t - s at tick t (bubble fraction (S-1)/(M+S-1)).

Used by the Hulk placement layer when the cost model picks pipeline for the
slow axis (placement.RuntimePlacement.pod_axis_strategy == "pipeline").
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

PyTree = Any


def gpipe_forward(stage_fn: Callable, mesh: Mesh, axis: str,
                  n_microbatches: int):
    """Build fn(stacked_params, x_microbatched) -> y_microbatched.

    * ``stage_fn(params_s, x)`` — one stage's computation (same signature on
      every stage; heterogeneous pipelines stack per-stage params).
    * stacked_params: every leaf (n_stages, ...) — sharded dim0 over `axis`.
    * x: (n_microbatches, mb_size, ...) — replicated over `axis`; stage 0
      consumes it, the last stage's outputs are collected and returned.
    """
    n_stages = mesh.shape[axis]

    def per_stage(params, x):
        # params: (1, ...) local slice -> squeeze; x: full (M, mb, ...)
        params = jax.tree.map(lambda p: p[0], params)
        stage_id = jax.lax.axis_index(axis)
        total = n_microbatches + n_stages - 1
        mb_shape = x.shape[1:]

        perm_fwd = [(i, i + 1) for i in range(n_stages - 1)]

        def tick(carry, t):
            state = carry            # (mb, ...) activation entering this stage
            # stage 0 injects microbatch t (valid while t < M)
            inject = x[jnp.minimum(t, n_microbatches - 1)]
            cur = jnp.where(stage_id == 0, inject, state)
            out = stage_fn(params, cur)
            # pass to next stage
            nxt = jax.lax.ppermute(out, axis, perm_fwd)
            # last stage emits microbatch t - (S-1) (valid when >= 0)
            return nxt, out

        state0 = jnp.zeros(mb_shape, x.dtype)
        _, outs = jax.lax.scan(tick, state0, jnp.arange(total))
        # outs: (T, mb, ...) — on the LAST stage, ticks S-1 .. T-1 hold the
        # final outputs of microbatches 0..M-1.
        y = jax.lax.dynamic_slice_in_dim(outs, n_stages - 1, n_microbatches,
                                         axis=0)
        # broadcast the last stage's result to every stage member so the
        # caller sees a replicated output (psum of a one-hot selection).
        is_last = (stage_id == n_stages - 1).astype(y.dtype)
        y = jax.lax.psum(y * is_last, axis)
        return y

    in_specs = (P(axis), P())        # params stacked over stages; x replicated
    out_specs = P()

    return shard_map(per_stage, mesh=mesh, in_specs=in_specs,
                     out_specs=out_specs, check_vma=False)


def gpipe_loss(stage_fn: Callable, loss_fn: Callable, mesh: Mesh, axis: str,
               n_microbatches: int):
    """fn(stacked_params, x_mb, target_mb) -> mean loss; differentiable
    end-to-end (grads flow through the ppermute chain)."""
    fwd = gpipe_forward(stage_fn, mesh, axis, n_microbatches)

    def fn(params, x, target):
        y = fwd(params, x)
        return loss_fn(y, target)

    return fn


def stack_stage_params(per_stage_params: list) -> PyTree:
    """[stage0_params, stage1_params, ...] -> stacked pytree (S, ...)."""
    return jax.tree.map(lambda *ls: jnp.stack(ls), *per_stage_params)


def stage_sharding(mesh: Mesh, axis: str, params_stacked: PyTree) -> PyTree:
    return jax.tree.map(
        lambda p: NamedSharding(mesh, P(axis, *([None] * (p.ndim - 1)))),
        params_stacked)


def microbatch(x: jnp.ndarray, n_microbatches: int) -> jnp.ndarray:
    """(B, ...) -> (M, B/M, ...)."""
    b = x.shape[0]
    assert b % n_microbatches == 0
    return x.reshape(n_microbatches, b // n_microbatches, *x.shape[1:])
