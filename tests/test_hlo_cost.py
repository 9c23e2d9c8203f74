"""Loop-aware HLO cost analyzer: trip-count multiplication must be exact
(XLA's own cost_analysis counts while bodies once — the bug this module
exists to fix)."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import hlo_cost


def _text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_scan_flops_multiplied():
    def scanned(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, None, length=7)
        return y

    x = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    w = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    res = hlo_cost.analyze(_text(scanned, x, w))
    expect = 7 * 2 * 256 ** 3
    assert abs(res["flops"] - expect) / expect < 1e-6
    assert res["unknown_trip_loops"] == 0
    # XLA's own count is 7x lower — the analyzer must disagree with it
    def one(x, w):
        return x @ w
    xla = jax.jit(one).lower(x, w).compile().cost_analysis()
    assert abs(float(xla["flops"]) * 7 - res["flops"]) / res["flops"] < 1e-6


def test_nested_scan_flops():
    def nested(x, w):
        def outer(c, _):
            def inner(c2, _):
                return c2 @ w, None
            c2, _ = jax.lax.scan(inner, c, None, length=3)
            return c2, None
        y, _ = jax.lax.scan(outer, x, None, length=5)
        return y

    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    w = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    res = hlo_cost.analyze(_text(nested, x, w))
    expect = 15 * 2 * 128 ** 3
    assert abs(res["flops"] - expect) / expect < 1e-6


def test_bytes_by_kind_present():
    def f(x, w):
        return jnp.tanh(x @ w)
    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    res = hlo_cost.analyze(_text(f, x, x))
    assert res["bytes"] > 0
    assert "dot" in res["bytes_by_kind"]


def test_shape_bytes():
    assert hlo_cost._shape_bytes("bf16[16,4096,128]{2,1,0}") \
        == 16 * 4096 * 128 * 2
    assert hlo_cost._shape_bytes("(f32[8]{0}, s32[])") == 36
    assert hlo_cost._shape_bytes("pred[]") == 1
