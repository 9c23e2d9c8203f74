"""jit wrapper: pad fleet-sized graphs to MXU tiles, backend selection."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.gcn_spmm import kernel as _k


@jax.jit
def spmm(adj, feats):
    """adj (N, N) @ feats (N, D) -> (N, D), any N/D (padded internally)."""
    n, d = feats.shape
    bi = min(_k.DEFAULT_BLOCK_I, max(8, 1 << (n - 1).bit_length()))
    bk = min(_k.DEFAULT_BLOCK_K, max(8, 1 << (n - 1).bit_length()))
    pad_n_i = (-adj.shape[0]) % bi
    pad_n_k = (-n) % bk
    pad_d = (-d) % 128
    a = jnp.pad(adj, ((0, pad_n_i), (0, pad_n_k)))
    h = jnp.pad(feats, ((0, pad_n_k), (0, pad_d)))
    interpret = jax.default_backend() != "tpu"
    o = _k.spmm_blocked(a, h, block_i=bi, block_k=bk, interpret=interpret)
    return o[:adj.shape[0], :d]


@jax.jit
def scaled_spmm(adj, feats, row_scale, col_scale):
    """(diag(row_scale) @ adj @ diag(col_scale)) @ feats -> (M, D) in one
    fused masked-aggregate op (degree / Kipf-Welling normalization rides
    inside the kernel). adj (M, N), feats (N, D), row_scale (M,),
    col_scale (N,); any shapes (padded internally, scales padded with 0 so
    padding rows/cols are inert)."""
    n, d = feats.shape
    bi = min(_k.DEFAULT_BLOCK_I, max(8, 1 << (n - 1).bit_length()))
    bk = min(_k.DEFAULT_BLOCK_K, max(8, 1 << (n - 1).bit_length()))
    pad_n_i = (-adj.shape[0]) % bi
    pad_n_k = (-n) % bk
    pad_d = (-d) % 128
    a = jnp.pad(adj, ((0, pad_n_i), (0, pad_n_k)))
    h = jnp.pad(feats, ((0, pad_n_k), (0, pad_d)))
    r = jnp.pad(row_scale.astype(feats.dtype), (0, pad_n_i))[:, None]
    c = jnp.pad(col_scale.astype(feats.dtype), (0, pad_n_k))[None, :]
    interpret = jax.default_backend() != "tpu"
    o = _k.scaled_spmm_blocked(a, h, r, c, block_i=bi, block_k=bk,
                               interpret=interpret)
    return o[:adj.shape[0], :d]
