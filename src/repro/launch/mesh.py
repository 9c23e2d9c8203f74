"""Production meshes. A FUNCTION (not a module-level constant) so importing
this module never touches jax device state.

Every mesh in the repo is built here, with Auto axes: the model code
annotates activations with ``with_sharding_constraint`` and lets GSPMD
propagate the rest, which Explicit axes (``jax.make_mesh``'s default) reject.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips (TPU v5e pod).
    Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the `pod` axis is
    the slow DCN axis — Hulk's placement puts only DP gradient reduction
    (or pipeline activations, cost-model-chosen) on it."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_mesh_for(devices_needed: int):
    """(data=N, model=1) over the first N available devices (N capped at the
    device count): the training launcher's data-parallel / FSDP mesh."""
    n = min(devices_needed, len(jax.devices()))
    return _mesh((n, 1), ("data", "model"))
