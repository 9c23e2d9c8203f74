"""JAX's persistent compilation cache for the entry points that run on a chip.

A cold run of a 32-layer prefill/decode spends most of its set-up compiling.
The cache key includes the cache path, so the directory is fixed: the one
``JAX_COMPILATION_CACHE_DIR`` names (JAX reads that variable itself), and
otherwise ``<repo>/.jax_cache`` (git-ignored).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory. Call before
    the first compile."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
