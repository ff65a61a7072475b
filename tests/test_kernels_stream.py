"""The weight-streaming projection (``kernels/stream.py``) in interpret
mode: the kernel against a bf16-operand dot, its tile picker, the dense
decode step with the streaming path forced on, and the engine's report
of which path its decode program takes."""
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.kernels.stream import (LANE, MAX_TILE_N, TILE_BYTES, pick_tile,
                                  stream_matmul, stream_tiles)
from repro.models import model as M
from repro.obs import Tracer, WallClock
from repro.serving import Request, ServingEngine

LAYERS = 3


def _bf16_dot(x, w_stack, layer):
    """What a default-precision f32 dot computes on the TPU."""
    w = w_stack[layer]
    return jnp.dot(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32)


def _f32_dot(x, w_stack, layer):
    return x @ w_stack[layer]


@pytest.mark.parametrize("m", [1, 4])
@pytest.mark.parametrize("kn", [(2048, 1024), (2048, 5632), (5632, 2048)],
                         ids=lambda kn: f"{kn[0]}x{kn[1]}")
def test_stream_matmul_matches_bf16_dot(kn, m):
    K, N = kn
    kx, kw = jax.random.split(jax.random.PRNGKey(K + N + m))
    x = jax.random.normal(kx, (m, K), jnp.float32)
    w = jax.random.normal(kw, (LAYERS, K, N), jnp.float32) / np.sqrt(K)
    fn = jax.jit(stream_matmul)
    for layer in range(LAYERS):
        got = fn(x, w, jnp.int32(layer))
        want = _bf16_dot(x, w, layer)
        assert got.shape == (m, N) and got.dtype == jnp.float32
        # same bf16 products, f32 sums in another order
        np.testing.assert_allclose(got, want, rtol=0,
                                   atol=1e-5 * float(jnp.abs(want).max()))
        # and the layer really is the one asked for
        other = _bf16_dot(x, w, (layer + 1) % LAYERS)
        assert float(jnp.abs(got - other).max()) > 0.1


@pytest.mark.parametrize("dim,tile", [(1024, 1024), (2048, 2048),
                                      (5632, 1408), (8192, 2048)])
def test_pick_tile(dim, tile):
    got = pick_tile(dim, MAX_TILE_N)
    assert got == tile
    assert got % LANE == 0 and dim % got == 0 and got <= MAX_TILE_N
    # the weight tile of every served shape fits the VMEM budget
    for other in (1024, 2048, 5632, 8192):
        for K, N in ((dim, other), (other, dim)):
            tk, tn = stream_tiles(K, N)
            assert K % tk == 0 and N % tn == 0
            assert tk % LANE == 0 and tn % LANE == 0
            assert tk * tn * 4 <= TILE_BYTES


def _tiny(name, n_kv_heads, qkv_bias):
    return ModelConfig(name=name, family="dense", n_layers=LAYERS,
                       d_model=256, n_heads=4, n_kv_heads=n_kv_heads,
                       head_dim=64, d_ff=384, vocab_size=512,
                       qkv_bias=qkv_bias)


TINY = {"gqa": _tiny("tiny-gqa", 2, False),
        "mha-bias": _tiny("tiny-mha-bias", 4, True)}


def _decode_steps(cfg, params, n_steps=3):
    """Logits of ``n_steps`` decode steps from an empty cache."""
    step = jax.jit(functools.partial(M.decode_step, cfg))
    cache = M.init_cache(cfg, 4, 16)
    out = []
    for t in range(n_steps):
        tokens = jnp.arange(4, dtype=jnp.int32) * 7 + t
        logits, cache = step(params, cache, tokens)
        out.append(logits)
    return jnp.stack(out)


@pytest.mark.parametrize("arch", sorted(TINY))
def test_decode_step_streams_the_block_weights(arch, monkeypatch):
    cfg = TINY[arch]
    params = M.init_params(cfg, jax.random.PRNGKey(1))
    if cfg.qkv_bias:
        attn = params["blocks"]["attn"]
        for i, b in enumerate(("bq", "bk", "bv")):
            attn[b] = 0.1 * jax.random.normal(jax.random.PRNGKey(10 + i),
                                              attn[b].shape)
    assert not M.streams_weights(cfg, params)        # the CPU keeps x @ w
    xla = _decode_steps(cfg, params)

    monkeypatch.setattr(M, "streams_weights", lambda cfg, params: True)
    # the plumbing: each projection reads its own weight at its own layer
    monkeypatch.setattr(M, "stream_matmul", _f32_dot)
    np.testing.assert_allclose(_decode_steps(cfg, params), xla,
                               rtol=1e-5, atol=1e-5)
    # the kernel: the step of bf16-operand dots, to f32 accumulation
    monkeypatch.setattr(M, "stream_matmul", _bf16_dot)
    want = _decode_steps(cfg, params)
    monkeypatch.setattr(M, "stream_matmul", stream_matmul)
    got = _decode_steps(cfg, params)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    assert float(jnp.abs(want - xla).max()) > 1e-3   # bf16 is not f32


def test_engine_on_cpu_leaves_the_dots_to_xla():
    cfg = TINY["gqa"]
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    tracer = Tracer(clock=WallClock())
    eng = ServingEngine(cfg, params, {"a-r8": 8}, max_batch=2, max_len=32,
                        tracer=tracer)
    assert eng.weight_stream is False
    with pytest.raises(AttributeError):
        eng.weight_stream = True
    eng.submit(Request(0, "a-r8", list(range(1, 9)), max_new_tokens=4,
                       arrival=time.monotonic()))
    eng.run_until_drained()
    disp = [s for s in tracer.spans if s.name == "decode.dispatch"]
    assert disp and all(s.attrs["weights"] == "xla" for s in disp)
