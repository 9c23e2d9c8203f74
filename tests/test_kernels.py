"""Per-kernel shape/dtype sweeps: Pallas interpret=True vs the pure-jnp
oracle, assert_allclose. Also checks the model-level flash/ref switch."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import ops as flash_ops
from repro.kernels.flash_attention import ref as flash_ref
from repro.kernels.decode_attention import kernel as decode_k
from repro.kernels.decode_attention import ops as dec_ops
from repro.kernels.decode_attention import ref as dec_ref
from repro.kernels.gcn_spmm import ops as spmm_ops
from repro.kernels.gcn_spmm import ref as spmm_ref


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
FLASH_CASES = [
    # (B, S, H, KV, D, window, dtype)
    (1, 128, 4, 4, 64, None, jnp.float32),
    (2, 256, 8, 2, 64, None, jnp.float32),      # GQA 4:1
    (1, 128, 4, 1, 128, None, jnp.float32),     # MQA
    (2, 192, 4, 4, 64, None, jnp.float32),      # non-pow2 seq (padding)
    (1, 256, 4, 2, 64, 64, jnp.float32),        # sliding window
    (1, 128, 8, 8, 64, None, jnp.bfloat16),
    (1, 64, 2, 2, 32, 16, jnp.bfloat16),        # small dims + window
]


@pytest.mark.parametrize("b,s,h,kv,d,window,dtype", FLASH_CASES)
def test_flash_attention_vs_ref(b, s, h, kv, d, window, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), dtype)
    k = jax.random.normal(ks[1], (b, s, kv, d), dtype)
    v = jax.random.normal(ks[2], (b, s, kv, d), dtype)
    got = flash_ops.flash_attention(q, k, v, window=window, block_q=64,
                                    block_kv=64)
    want = flash_ref.attention_ref(q, k, v, window=window)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_flash_matches_model_attention():
    """The kernel must agree with the model's einsum attention path."""
    from repro.configs.base import AttnSpec
    from repro.models import attention as attn_mod
    spec = AttnSpec(n_heads=4, n_kv_heads=2, head_dim=32)
    b, s = 2, 64
    key = jax.random.PRNGKey(3)
    p = attn_mod.init_attn(key, spec, 64, jnp.float32)
    x = jax.random.normal(key, (b, s, 64), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    y_ref = attn_mod.attn_full(p, spec, x, positions)
    attn_mod.FLAGS["use_flash"] = True
    try:
        y_flash = attn_mod.attn_full(p, spec, x, positions)
    finally:
        attn_mod.FLAGS["use_flash"] = False
    np.testing.assert_allclose(np.asarray(y_flash), np.asarray(y_ref),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------
DEC_CASES = [
    # (B, T, H, KV, D, n_valid, dtype); k/v (B, KV, D, T), slots minor
    (1, 256, 4, 4, 64, 200, jnp.float32),
    (2, 512, 8, 2, 64, 512, jnp.float32),
    (1, 384, 4, 1, 128, 100, jnp.float32),     # MQA, non-pow2 T
    (2, 256, 8, 8, 64, 17, jnp.bfloat16),
]


def _check_decode(q, k, v, valid, layer=None, scale=None):
    """The wrapper (blocks from the shapes) and the kernel at 128-slot
    blocks, one kv head a step, against the reference."""
    want = np.asarray(dec_ref.decode_attention_ref(q, k, v, valid, layer,
                                                   scale=scale), np.float32)
    got = dec_ops.decode_attention(q, k, v, valid, layer, scale=scale)
    b, _, h, d = q.shape
    kvh = k.shape[-3]
    stacked = k if layer is not None else k[None]
    blocked = decode_k.decode_attention_grouped(
        q.reshape(b, kvh, h // kvh, d), stacked,
        v if layer is not None else v[None],
        (valid > 0).astype(jnp.int32)[None],
        jnp.full((1,), 0 if layer is None else layer, jnp.int32),
        block_kv=128, scale=scale).reshape(b, 1, h, d)
    for out in (got, blocked):
        np.testing.assert_allclose(np.asarray(out, np.float32), want,
                                   **_tol(q.dtype))


@pytest.mark.parametrize("b,t,h,kv,d,nv,dtype", DEC_CASES)
def test_decode_attention_vs_ref(b, t, h, kv, d, nv, dtype):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (b, 1, h, d), dtype)
    k = jax.random.normal(ks[1], (b, kv, d, t), dtype)
    v = jax.random.normal(ks[2], (b, kv, d, t), dtype)
    valid = (jnp.arange(t) < nv).astype(jnp.int32)
    _check_decode(q, k, v, valid)


def _ring_valid(t):
    """A wrapped ring cache's mask: slots 50..119 stale, the rest live."""
    s = jnp.arange(t)
    return ((s < 50) | (s >= 120)).astype(jnp.int32)


STACKED_CASES = {
    # name: (L, layer, B, T, H, KV, D, valid(T), K = V, scale)
    "first_layer": (3, 0, 2, 256, 8, 2, 64, lambda t: jnp.arange(t) < 200,
                    False, None),
    "last_layer": (3, 2, 2, 256, 8, 2, 64, lambda t: jnp.arange(t) < 200,
                   False, None),
    "ring_mask": (3, 1, 2, 256, 4, 4, 32, _ring_valid, False, None),
    "mla_shaped": (3, 1, 1, 384, 16, 1, 576, lambda t: jnp.arange(t) < 300,
                   True, 0.07),
}


@pytest.mark.parametrize("case", sorted(STACKED_CASES))
def test_decode_attention_stacked_read(case):
    """The kernel reads layer ``layer`` of a layer-stacked (L, B, KV, D, T)
    cache in place, by index."""
    n, layer, b, t, h, kv, d, valid_fn, shared, scale = STACKED_CASES[case]
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (b, 1, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (n, b, kv, d, t), jnp.float32)
    v = k if shared else jax.random.normal(ks[2], k.shape, jnp.float32)
    _check_decode(q, k, v, valid_fn(t).astype(jnp.int32), jnp.int32(layer),
                  scale)


def test_decode_matches_full_last_position():
    """Flash-decode at position S-1 == full attention's last row."""
    b, s, h, kv, d = 1, 128, 4, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, kv, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, kv, d), jnp.float32)
    full = flash_ref.attention_ref(q, k, v)
    valid = jnp.ones((s,), jnp.int32)
    got = dec_ops.decode_attention(q[:, -1:], k.transpose(0, 2, 3, 1),
                                   v.transpose(0, 2, 3, 1), valid)
    np.testing.assert_allclose(np.asarray(got[:, 0]), np.asarray(full[:, -1]),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# gcn spmm
# ---------------------------------------------------------------------------
SPMM_CASES = [
    (8, 22, jnp.float32),        # paper fig1 scale
    (46, 15, jnp.float32),       # fleet46 scale
    (128, 213, jnp.float32),     # gnn hidden width
    (200, 64, jnp.float32),      # multi-block rows
    (46, 12, jnp.bfloat16),
]


@pytest.mark.parametrize("n,d,dtype", SPMM_CASES)
def test_spmm_vs_ref(n, d, dtype):
    ks = jax.random.split(jax.random.PRNGKey(4), 2)
    adj = (jax.random.uniform(ks[0], (n, n)) < 0.4).astype(dtype) * \
        jax.random.uniform(ks[0], (n, n)).astype(dtype)
    h = jax.random.normal(ks[1], (n, d), dtype)
    got = spmm_ops.spmm(adj, h)
    want = spmm_ref.spmm_ref(adj, h)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_gnn_pallas_path_matches():
    """GNNConfig(use_pallas=True) must give the same logits as the jnp path."""
    from repro.core import gnn
    from repro.core.graph import paper_fig1_graph
    g = paper_fig1_graph()
    feats = jnp.asarray(g.node_features())
    lat = jnp.asarray(g.latency.astype(np.float32))
    cfg_j = gnn.GNNConfig(n_classes=3, use_pallas=False)
    cfg_p = gnn.GNNConfig(n_classes=3, use_pallas=True)
    params = gnn.init(jax.random.PRNGKey(0), cfg_j, feats.shape[1])
    out_j = gnn.apply(params, cfg_j, feats, lat)
    out_p = gnn.apply(params, cfg_p, feats, lat)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_j),
                               rtol=1e-5, atol=1e-5)
