"""Real-JAX serving engine: determinism vs direct decode, co-batching
isolation, drain behavior."""
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import model as M
from repro.obs import Tracer, WallClock
from repro.serving import Request, ServingEngine

ADAPTERS = {"a-r8": 8, "b-r64": 64}


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("llama-7b-paper")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _mk_engine(cfg, params, **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_len", 48)
    return ServingEngine(cfg, params, ADAPTERS, **kw)


def test_engine_matches_direct_decode(setup):
    cfg, params = setup
    eng = _mk_engine(cfg, params)
    prompt = list(range(1, 9))
    toks = jnp.asarray([prompt], jnp.int32)
    logits, cache = M.prefill(cfg, params, toks, bank=eng.bank,
                              lora_idx=jnp.asarray([0]), cache_len=48,
                              cache_dtype=jnp.float32)
    want = [int(jnp.argmax(logits[0]))]
    for _ in range(4):
        l2, cache = M.decode_step(cfg, params, cache,
                                  jnp.asarray([want[-1]], jnp.int32),
                                  bank=eng.bank,
                                  lora_idx=jnp.asarray([0]))
        want.append(int(jnp.argmax(l2[0])))
    req = Request(0, "a-r8", prompt, max_new_tokens=5,
                  arrival=time.monotonic())
    eng.submit(req)
    eng.run_until_drained()
    assert req.output == want


def test_cobatching_preserves_outputs(setup):
    """A request's tokens are identical whether decoded alone or
    co-batched with a different-rank adapter (the interference is a
    *performance* effect, never a numerical one)."""
    cfg, params = setup
    prompt_a = list(range(1, 9))
    prompt_b = list(range(3, 14))

    solo = _mk_engine(cfg, params)
    ra = Request(0, "a-r8", prompt_a, 5, arrival=time.monotonic())
    solo.submit(ra)
    solo.run_until_drained()

    both = _mk_engine(cfg, params)
    ra2 = Request(0, "a-r8", prompt_a, 5, arrival=time.monotonic())
    rb2 = Request(1, "b-r64", prompt_b, 5, arrival=time.monotonic())
    both.submit(ra2)
    both.submit(rb2)
    both.run_until_drained()
    assert ra2.output == ra.output


def test_engine_drains_and_reports_metrics(setup):
    cfg, params = setup
    eng = _mk_engine(cfg, params)
    now = time.monotonic()
    for i in range(6):
        eng.submit(Request(i, ["a-r8", "b-r64"][i % 2],
                           list(range(1, 8 + i)), 4, arrival=now))
    summ = eng.run_until_drained()
    assert summ["finished"] == 6
    assert summ["p95_ttft"] > 0
    assert eng.active == 0 and not eng.queue


def test_bank_max_rank_padding(setup):
    cfg, params = setup
    eng = _mk_engine(cfg, params)
    assert eng.max_rank == 64
    # bank A tensors padded to max rank
    a = eng.bank["q"]["A"]
    assert a.shape[-1] == 64


def test_every_dot_is_scoped(setup):
    """The device trace reads each op's scope from its op_name: every
    matmul of the decode and prefill programs carries one of the five
    names."""
    cfg, params = setup
    eng = _mk_engine(cfg, params)
    scopes = {"proj", "lora", "attention", "mlp", "lm_head"}
    dec = eng._decode.lower(eng.params, eng.cache, eng.last_token,
                            eng.bank, eng._slot_lora).compile().as_text()
    idx = eng.lora_bank.lora_idx(jnp.zeros((2,), jnp.int32))
    pre = eng._prefill_fn(8).lower(
        eng.params, jnp.ones((2, 8), jnp.int32), eng.bank,
        idx).compile().as_text()
    for text in (dec, pre):
        names = [re.search(r'op_name="([^"]*)"', line).group(1)
                 for line in text.splitlines() if " dot(" in line]
        assert names
        for name in names:
            assert scopes & set(name.split("/")), name
        found = {s for name in names for s in scopes
                 if s in name.split("/")}
        assert found == scopes


@pytest.mark.parametrize("decode_block", [1, 3])
def test_dispatch_reports_the_live_kv(setup, decode_block):
    """Each ``decode.dispatch`` names the attention path (``xla`` on the
    CPU) and ``kv_live``: the slots' live tokens over max_batch x
    max_len, which is what the device's positions say the attention
    reads (a free slot's position is parked past the cache: none)."""
    cfg, params = setup
    tracer = Tracer(clock=WallClock())
    eng = _mk_engine(cfg, params, max_batch=3, tracer=tracer,
                     decode_block=decode_block)
    assert eng.kv_attention == "xla"
    seen = []
    decode_k = eng._decode_k_fn(decode_block)

    def watch(fn):
        def call(params, cache, *args):
            pos = np.asarray(cache["pos"])
            seen.append(int(np.where(pos < eng.max_len, pos + 1, 0).sum()))
            return fn(params, cache, *args)
        return call

    eng._decode = watch(eng._decode)
    eng._decode_k_cache[decode_block] = watch(decode_k)
    for i, (n, out) in enumerate([(8, 3), (13, 9), (5, 4), (21, 2)]):
        eng.submit(Request(i, "a-r8", list(range(1, n + 1)),
                           max_new_tokens=out, arrival=time.monotonic()))
    eng.run_until_drained()
    disp = [s for s in tracer.spans if s.name == "decode.dispatch"]
    assert len(disp) == len(seen) > 2
    assert all(s.attrs["attention"] == "xla" for s in disp)
    assert [s.attrs["kv_live"] for s in disp] == [
        n / (3 * eng.max_len) for n in seen]
    # and some dispatches ran with a slot free
    assert any(s.attrs["batch"] < 3 for s in tracer.spans
               if s.name == "decode" and s.cat == "iteration")
