"""A run with the served path broken underneath comes out not correct:
the LoRA delta left out, a token altered where the engine produces it,
and a decode step that returns its cache unchanged."""
import contextlib

import pytest

from chipbench import harness
from chipbench.tests.helpers import SECONDS, SEED, smoke_cell


@contextlib.contextmanager
def _patched(obj, name, fn):
    saved = getattr(obj, name)
    setattr(obj, name, fn)
    try:
        yield
    finally:
        setattr(obj, name, saved)


def no_lora_delta():
    import jax.numpy as jnp
    from repro.lora import batched

    def zero(x, A, B, idx, scaling=1.0):
        return jnp.zeros(x.shape[:-1] + (B.shape[-1],), x.dtype)

    return _patched(batched, "lora_delta", zero)


def altered_token():
    from repro.serving.engine import ServingEngine
    real = ServingEngine._finish_token

    def finish(self, slot, req, token, now):
        if len(req.output) == 3:
            token = (token + 1) % self.cfg.vocab_size
        return real(self, slot, req, token, now)

    return _patched(ServingEngine, "_finish_token", finish)


def stale_cache():
    from repro.models import model as M
    real = M.decode_step

    def step(cfg, params, cache, tokens, **kw):
        logits, _ = real(cfg, params, cache, tokens, **kw)
        return logits, cache

    return _patched(M, "decode_step", step)


@pytest.mark.parametrize("fault", [no_lora_delta, altered_token,
                                   stale_cache])
def test_fault_is_not_correct(tmp_path, fault):
    cell = smoke_cell(tmp_path)
    res = harness.run_cell(cell, SEED, SECONDS, False, break_program=fault)
    assert res["correct"] is False, res["checks"]
