"""Operation and byte counts against hand counts at a small shape."""
import pytest

from chipbench import counts

M = counts.Decoder(d=8, layers=2, heads=4, kv_heads=2, head_dim=2, d_ff=16,
                   vocab=32, gated=True)


def test_layer_params():
    # wq 8x8, wk and wv 8x4, wo 8x8, three 8x16 MLP matrices
    assert M.layer_matmul_params == 64 + 32 + 32 + 64 + 3 * 128


def test_flash_attention():
    # batch 1, seq 3: 6 causal pairs x 4 heads x 2 dims x 2 matmuls x 2 ops
    w = counts.flash_attention(M, batch=1, seq=3)
    assert w.flops == 2 * (6 * 4 * 2 * 2 * 2)
    # q and o: 3 x 4 x 2 each, k and v: 3 x 2 x 2 each, 2 bytes, 2 layers
    assert w.bytes == 2 * 2 * (24 + 24 + 12 + 12)


def test_decode_attention():
    w = counts.decode_attention_step(M, batch=1, filled=5)
    assert w.flops == 2 * (2 * 2 * 4 * 2 * 5)
    assert w.bytes == 2 * 2 * (2 * 5 * 2 * 2 + 2 * 4 * 2)
    call = counts.decode_attention_call(M, batch=1, prompt=4, gen=3)
    both = (counts.decode_attention_step(M, 1, 5)
            + counts.decode_attention_step(M, 1, 6))
    assert (call.flops, call.bytes) == (both.flops, both.bytes)


def test_serve_call_flops():
    per_token = 2 * M.layer_matmul_params * M.layers
    head = 2 * M.d * M.vocab
    want = (4 * per_token + head + counts.flash_attention(M, 1, 4).flops
            + 2 * (per_token + head)
            + counts.decode_attention_call(M, 1, 4, 3).flops)
    assert counts.serve_call_flops(M, 1, 4, 3) == want


def test_roofline_bound():
    w = counts.Work(flops=2e12, bytes=1e9)
    assert w.seconds(1e12, 1e9) == pytest.approx(2.0)
    assert w.seconds(1e13, 1e8) == pytest.approx(10.0)
