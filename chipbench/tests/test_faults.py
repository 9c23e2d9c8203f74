"""A run whose timed path is broken underneath comes out not correct: the
harness skips its look for a chip and drives the rest of the run at CPU
size, once for each fault the cell can have."""
import numpy as np
import pytest

import repro.launch.serve as serve_mod
from chipbench import run
from chipbench.tests.helpers import args, tiny_copy


def _alter_token(orig):
    def serve_batch(cfg, params, batch, gen, log=print):
        out, stats = orig(cfg, params, batch, gen, log)
        out = out.copy()
        out[:, gen // 2] = (out[:, gen // 2] + 1) % cfg.vocab_size
        return out, stats
    return serve_batch


def _half_batch(orig):
    def serve_batch(cfg, params, batch, gen, log=print):
        half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
        out, stats = orig(cfg, params, half, gen, log)
        return np.concatenate([out, out]), stats
    return serve_batch


def _stale_cache(orig):
    def make_decode_step(cfg, api=None):
        step = orig(cfg, api)

        def stale(params, token, pos, caches):
            nxt, _ = step(params, token, pos, caches)
            return nxt, caches
        return stale
    return make_decode_step


SERVE_FAULTS = {
    "token_altered": ("serve_batch", _alter_token),
    "half_batch_left_out": ("serve_batch", _half_batch),
    "state_unchanged": ("make_decode_step", _stale_cache),
}


@pytest.mark.parametrize("cell", ["phi3.decode", "starcoder2.decode"])
@pytest.mark.parametrize("fault", sorted(SERVE_FAULTS))
def test_serve_fault_is_caught(tmp_path, monkeypatch, cell, fault):
    name, wrap = SERVE_FAULTS[fault]
    monkeypatch.setattr(serve_mod, name, wrap(getattr(serve_mod, name)))
    out = run.run(args(cell), root=tiny_copy(tmp_path), require_tpu=False)
    assert not out["correct"], out["checks"]
