"""Traffic made from ``--seed``: prompt batches for the serve cells.

The generator is a copy, kept here so that a change to the program cannot
move the yardstick: ``prompts`` is the token stream of
``repro.data.synthetic.make_batch``.
"""
from __future__ import annotations

import jax
import numpy as np

SEED_WORDS = 2   # a seed of up to 64 bits becomes two 32-bit words


def seed_words(seed: int) -> list[int]:
    """A non-negative seed of up to 64 bits as 32-bit words, for numpy's
    ``SeedSequence`` and for JAX keys."""
    if seed < 0 or seed >= 1 << 64:
        raise ValueError(f"seed {seed} is not a whole number below 2**64")
    return [(seed >> (32 * i)) & 0xFFFFFFFF for i in range(SEED_WORDS)]


def jax_key(seed: int):
    """A JAX PRNG key from a seed of up to 64 bits."""
    w0, w1 = seed_words(seed)
    return jax.random.fold_in(jax.random.PRNGKey(w0), w1)


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """Independent numpy stream per (seed, stream...)."""
    return np.random.default_rng(np.random.SeedSequence(
        entropy=seed_words(seed), spawn_key=tuple(stream)))


# ---------------------------------------------------------------------------
# Serving: prompt batches
# ---------------------------------------------------------------------------
PROMPT_STREAM = 1


def prompts(vocab: int, batch: int, length: int, seed: int,
            call: int) -> np.ndarray:
    """(batch, length) int32 prompt tokens of one call: a random start per row
    and a drift of 0..16 per position, modulo the vocabulary, as
    ``make_batch`` draws them. Every call has the same shape; the seed and the
    call index choose the tokens."""
    rng = rng_for(seed, PROMPT_STREAM, call)
    base = rng.integers(0, vocab, size=(batch, 1), dtype=np.int64)
    drift = rng.integers(0, 17, size=(batch, length), dtype=np.int64)
    return ((base + np.cumsum(drift, axis=1)) % vocab).astype(np.int32)
