"""Compile-only guards for a described TPU v5e chip at real widths.

Nothing runs on a chip: each case lowers and compiles for the described
device, which raises what the chip's compiler would raise (tiling, VMEM,
memory). Kernel cases also check that the Pallas kernel is in the program
(``tpu_custom_call``); the CPU tests only ever run the kernels interpreted.

The topology is described inside a fixture, never at import, so every xdist
worker collects the same tests and only the worker given this file loads the
TPU library. Keep every such compile in this one file.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.configs.base import Segment
from repro.kernels.decode_attention import kernel as decode_k
from repro.kernels.flash_attention import kernel as flash_k
from repro.kernels.gcn_spmm import kernel as spmm_k
from repro.launch import specs as sp
from repro.launch.serve import serving_runtime
from repro.launch.train import train_runtime
from repro.models.common import use_runtime
from repro.training.optimizer import AdamWConfig
from repro.training.train_step import make_train_step


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as ccache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # else libtpu logs under /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler installed
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent cache
        # but cannot be read back without one
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        ccache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            ccache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("n", [64, 512])
def test_scaled_spmm_compiles(one_chip, n):
    """GCN aggregation at planner sizes; hidden 213 is padded to 256."""
    block = min(spmm_k.DEFAULT_BLOCK_I, max(8, 1 << (n - 1).bit_length()))
    d = 256
    args = (_spec((n, n), jnp.float32, one_chip),
            _spec((n, d), jnp.float32, one_chip),
            _spec((n, 1), jnp.float32, one_chip),
            _spec((1, n), jnp.float32, one_chip))
    text = _compiled_text(
        lambda a, h, r, c: spmm_k.scaled_spmm_blocked(
            a, h, r, c, block_i=block, block_k=block, interpret=False), *args)
    assert "tpu_custom_call" in text


def test_flash_prefill_compiles_at_phi3_widths(one_chip):
    b, h, s, d = 1, 32, 2048, 96
    q = _spec((b, h, s, d), jnp.bfloat16, one_chip)
    kv = _spec((b, h, s, d), jnp.bfloat16, one_chip)
    text = _compiled_text(
        lambda q, k, v: flash_k.flash_attention_bhsd(
            q, k, v, causal=True, interpret=False), q, kv, kv)
    assert "tpu_custom_call" in text


def test_grouped_decode_compiles_at_phi3_widths(one_chip):
    """The kernel reads layer 1 of a two-layer stacked cache held slots
    minor, (L, B, KV, D, T)."""
    n, b, kvh, g, t, d = 2, 4, 32, 1, 2048, 96
    q = _spec((b, kvh, g, d), jnp.bfloat16, one_chip)
    kv = _spec((n, b, kvh, d, t), jnp.bfloat16, one_chip)
    valid = _spec((1, t), jnp.int32, one_chip)
    layer = _spec((1,), jnp.int32, one_chip)
    text = _compiled_text(
        lambda q, k, v, m, i: decode_k.decode_attention_grouped(
            q, k, v, m, i, interpret=False),
        q, kv, kv, valid, layer)
    assert "tpu_custom_call" in text


def test_train_step_grad_compiles_at_phi3_widths(one_chip, monkeypatch):
    """One phi3-width layer under ``jax.grad`` with the runtime
    ``train_loop`` traces under. The kernel wrappers are steered to the
    compiled kernels, so a forward-only kernel on this path fails here at
    trace time."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    seq_len = 1024
    cfg = get_config("phi3-mini-3.8b")
    cfg = dataclasses.replace(cfg, segments=(
        Segment(count=1, layers=cfg.segments[0].layers),))
    opt = AdamWConfig(total_steps=10)
    place = lambda t: jax.tree.map(                               # noqa: E731
        lambda s: _spec(s.shape, s.dtype, one_chip), t)
    state = place(sp.train_state_struct(cfg, opt))
    batch = place({"tokens": jax.ShapeDtypeStruct((1, seq_len), jnp.int32),
                   "labels": jax.ShapeDtypeStruct((1, seq_len), jnp.int32)})
    with use_runtime(train_runtime(seq_len)):
        lowered = jax.jit(make_train_step(cfg, opt),
                          donate_argnums=(0,)).lower(state, batch)
    assert lowered.compile().memory_analysis().argument_size_in_bytes > 0


def _check_stacked_read(text: str, stacked):
    """The compiled decode step reads the layer-stacked cache ``stacked``
    (a ShapeDtypeStruct) where it lies. Nothing copies it whole; no op makes
    a one-layer slab of it (a bf16 array of the stacked shape's sizes less
    the layer axis, in any order, with or without unit axes); nothing is
    padded under ``jit(decode_attention)``; and every decode kernel call
    takes the stacked array itself as K and V."""
    shape = "bf16[" + ",".join(map(str, stacked.shape)) + "]"
    lines = text.splitlines()
    copies = [line for line in lines
              if shape in line.split(" copy", 1)[0]
              and (" copy(" in line or " copy-start(" in line)]
    assert not copies, copies[:2]
    one_layer = sorted(n for n in stacked.shape[1:] if n != 1)
    slabs = [line for line in lines
             for dims in re.findall(r"= bf16\[([\d,]+)\]", line)
             if sorted(int(n) for n in dims.split(",") if n != "1")
             == one_layer]
    assert not slabs, slabs[:2]
    pads = [line for line in lines
            if " pad(" in line and "jit(decode_attention)" in line]
    assert not pads, pads[:2]
    calls = [line for line in lines if 'custom_call_target="tpu_custom_call"'
             in line and "decode_attention" in line]
    assert calls
    assert all(line.split("custom-call(", 1)[1].count(shape) == 2
               for line in calls), calls[:1]


def _row_major_default(stacked, one_chip) -> bool:
    """Whether the chip's default layout for ``stacked``'s shape is row-major:
    then ``decoder_lm._default_layout`` holds the scan's carry in the layout
    the decode kernel reads, and no copy enters the loop."""
    from jax.experimental.layout import Layout
    dev = next(iter(one_chip.device_set))
    pjrt = dev.client.get_default_layout(stacked.dtype, stacked.shape, dev)
    order = Layout.from_pjrt_layout(pjrt).major_to_minor
    return tuple(order) == tuple(range(len(stacked.shape)))


@pytest.mark.parametrize("arch", ["phi3-mini-3.8b", "starcoder2-3b"])
def test_decode_step_writes_stacked_cache_in_place(one_chip, monkeypatch,
                                                   arch):
    """The serving decode step at real widths and depth, batch 8, 1280 cache
    slots, through the compiled decode kernel. (At four layers starcoder2's
    stacked cache fits the chip's VMEM, and XLA stages it there whole for
    the kernel: a copy the real depth does not have.) The caches are held
    slots minor, (L, B, KV, D, T), whose default layout on the chip is
    row-major; the layer scan carries them in that layout, each layer writes
    its slot column in place, and the decode kernel reads the layer's K/V
    out of the stacked arrays by index: nothing copies, slices, relays or
    pads a cache between the scan's carry and the kernel, and the step's
    scratch memory stays below one stacked cache."""
    from repro.models import decoder_lm as dlm
    from repro.training.train_step import make_decode_step
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "devices", lambda *a: list(one_chip.device_set))
    cfg = get_config(arch)
    assert len(cfg.segments) == 1
    n = cfg.segments[0].count
    place = lambda t: jax.tree.map(                               # noqa: E731
        lambda s: _spec(s.shape, s.dtype, one_chip), t)
    params = place(sp.param_struct(cfg))
    caches = place(jax.eval_shape(lambda: dlm.init_caches(cfg, 8, 1280)))
    token = _spec((8, 1), jnp.int32, one_chip)
    pos = _spec((), jnp.int32, one_chip)
    with use_runtime(serving_runtime("tpu")):
        lowered = jax.jit(make_decode_step(cfg), donate_argnums=(3,)).lower(
            params, token, pos, caches)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    k = caches[0][0]["k"]
    attn = cfg.segments[0].layers[0].attn
    assert k.shape == (n, 8, attn.n_kv_heads, attn.head_dim, 1280)
    assert _row_major_default(k, one_chip)
    _check_stacked_read(text, k)
    assert compiled.memory_analysis().temp_size_in_bytes < k.size * 2


def _deepseek_share(count: int, moe: bool):
    """DeepSeek-V2 at published widths, ``count`` stacked MLA layers, the
    MoE ones holding experts 0..19 of the router's 160."""
    cfg = get_config("deepseek-v2-236b")
    layer = cfg.segments[1 if moe else 0].layers[0]
    if moe:
        layer = dataclasses.replace(layer, moe=dataclasses.replace(
            layer.moe, first_local=0, n_local=20))
    return dataclasses.replace(cfg, segments=(Segment(count=count,
                                                      layers=(layer,)),))


def test_absorbed_mla_decode_step_compiles(one_chip, monkeypatch):
    """The absorbed MLA decode step at published widths (128 heads over one
    latent head of 576), two stacked layers, batch 32, 768 slots, through
    the compiled decode kernel: each latent slot column is written in place
    into the stacked (L, B, 1, 576, T) cache, and the kernel reads it there
    as K and V, with no slab, relayout or pad in between."""
    from repro.models import decoder_lm as dlm
    from repro.training.train_step import make_decode_step
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "devices", lambda *a: list(one_chip.device_set))
    cfg = _deepseek_share(2, moe=False)
    place = lambda t: jax.tree.map(                               # noqa: E731
        lambda s: _spec(s.shape, s.dtype, one_chip), t)
    params = place(sp.param_struct(cfg))
    caches = place(jax.eval_shape(lambda: dlm.init_caches(cfg, 32, 768)))
    with use_runtime(serving_runtime("tpu")):
        lowered = jax.jit(make_decode_step(cfg), donate_argnums=(3,)).lower(
            params, _spec((32, 1), jnp.int32, one_chip),
            _spec((), jnp.int32, one_chip), caches)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    latent = caches[0][0]["latent"]
    assert latent.shape == (2, 32, 1, 576, 768)
    assert _row_major_default(latent, one_chip)
    _check_stacked_read(text, latent)


@pytest.mark.parametrize("tokens", [32, 16384], ids=["decode", "prefill"])
def test_dropless_moe_layer_compiles(one_chip, tokens):
    """The dropless MoE layer at published widths, 20 of 160 experts held,
    for a decode step (batch 32) and a prefill (32 x 512): the expert
    matmuls lower to the TPU's grouped matmul (``ragged-dot``)."""
    from repro.models import mlp as mlp_mod
    spec = _deepseek_share(1, moe=True).segments[0].layers[0].moe
    params = jax.eval_shape(lambda k: mlp_mod.init_moe(
        k, spec, 5120, "silu", jnp.bfloat16), jax.random.PRNGKey(0))
    params = jax.tree.map(lambda s: _spec(s.shape, s.dtype, one_chip),
                          params)
    assert params["w_up"].shape == (20, 5120, 1536)
    x = _spec((32, tokens // 32, 5120), jnp.bfloat16, one_chip)
    text = _compiled_text(
        lambda p, x: mlp_mod.moe_dropless(p, spec, x, "silu"), params, x)
    assert "ragged-dot" in text
