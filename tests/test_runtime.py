"""The runtime each launcher traces its programs under, and that a launcher
leaves none set behind."""
import dataclasses

import jax
import jax.numpy as jnp

from repro.configs import get_config, reduce_for_smoke
from repro.kernels.decode_attention import ops as dec_ops
from repro.launch.serve import serve_batch, serving_runtime
from repro.launch.train import train_runtime
from repro.models.common import Runtime, runtime
from repro.models.registry import get_api


def test_launchers_choose_their_runtime():
    assert serving_runtime("tpu") == Runtime(kernels=True, seq_chunk=0)
    assert serving_runtime("cpu") == Runtime(kernels=False, seq_chunk=0)
    assert train_runtime(512) == Runtime(kernels=False, seq_chunk=0)
    assert train_runtime(1024) == Runtime(kernels=False, seq_chunk=256)


def test_serve_batch_traces_under_its_runtime_and_leaves_none(monkeypatch):
    """With ``runtime=Runtime(kernels=True)`` on the CPU, decode goes
    through the (interpreted) decode kernel; afterwards the default
    runtime is back."""
    seen = []
    kernel = dec_ops.decode_attention

    def counted(*args, **kw):
        seen.append(runtime())
        return kernel(*args, **kw)

    monkeypatch.setattr(dec_ops, "decode_attention", counted)
    cfg = dataclasses.replace(reduce_for_smoke(get_config("phi3-mini-3.8b")),
                              remat=False)
    params = get_api(cfg).init_params(cfg, jax.random.PRNGKey(0))
    gen, _ = serve_batch(cfg, params, {"tokens": jnp.ones((2, 8), jnp.int32)},
                         3, log=lambda *a: None,
                         runtime=Runtime(kernels=True))
    assert gen.shape == (2, 3)
    assert seen and all(rt == Runtime(kernels=True) for rt in seen)
    assert runtime() == Runtime()
