"""The weight-streaming projection (``kernels/stream.py``) in interpret
mode: the kernel against a bf16-operand dot, its tile picker, the dense
decode step with the streaming path forced on, and the engine's report
of which path its decode program takes."""
import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.configs.base import ModelConfig
from repro.kernels.stream import (LANE, MAX_TILE_N, TILE_BYTES, pick_tile,
                                  stream_matmul, stream_tiles, vmem_bytes)
from repro.models import model as M
from repro.obs import Tracer, WallClock
from repro.serving import Request, ServingEngine

LAYERS = 3


def _bf16_dot(x, w_stack, layer):
    """What a default-precision f32 dot computes on the TPU (an expert
    stack's layer gives every expert's product)."""
    w = w_stack[layer]
    return jnp.matmul(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
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


@pytest.mark.parametrize("kn", [(2048, 576), (10944, 256), (256, 10944)],
                         ids=lambda kn: f"{kn[0]}x{kn[1]}")
def test_stream_matmul_off_the_lane(kn):
    """Dims that are no multiple of 128 (MLA's latent and rope key, 576;
    DeepSeek-V2-Lite's dense layer, 10944) are taken whole."""
    K, N = kn
    tk, tn = stream_tiles(K, N)
    assert K % tk == 0 and N % tn == 0
    assert (tk == K) if K % LANE else (tk % LANE == 0)
    assert (tn == N) if N % LANE else (tn % LANE == 0)
    kx, kw = jax.random.split(jax.random.PRNGKey(K + 3 * N))
    x = jax.random.normal(kx, (4, K), jnp.float32)
    w = jax.random.normal(kw, (2, K, N), jnp.float32) / np.sqrt(K)
    got = jax.jit(stream_matmul)(x, w, jnp.int32(1))
    want = _bf16_dot(x, w, 1)
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=1e-5 * float(jnp.abs(want).max()))


@pytest.mark.parametrize("kn", [(2048, 576), (2048, 10944), (10944, 2048),
                                (2048, 1408), (1408, 2048), (2048, 2816),
                                (2816, 2048), (2048, 3072)],
                         ids=lambda kn: f"{kn[0]}x{kn[1]}")
def test_served_deepseek_tiles_fit_vmem(kn):
    """Every DeepSeek-V2-Lite weight the decode step streams: the
    tiles divide it, and a tile over ``TILE_BYTES`` (a whole dim off
    the lane) is one lane wide; the blocks at batch 8 fit v5e's VMEM
    (128 MiB), within the limit the call then asks for."""
    K, N = kn
    tk, tn = stream_tiles(K, N)
    assert K % tk == 0 and N % tn == 0
    if tk * tn * 4 > TILE_BYTES:
        assert (K % LANE and tn == LANE) or (N % LANE and tk == LANE)
    assert vmem_bytes(8, tk, tn) < 32 * 2**20


@pytest.mark.parametrize("shared_x", [True, False],
                         ids=["x-shared", "x-per-expert"])
def test_stream_matmul_expert_stack(shared_x):
    """An expert stack (L, E, K, N): every expert of the layer asked
    for, in one call, from one x or one x per expert."""
    L, E, K, N, M = 2, 3, 256, 1408, 4
    kx, kw = jax.random.split(jax.random.PRNGKey(7))
    w = jax.random.normal(kw, (L, E, K, N), jnp.float32) / np.sqrt(K)
    shape = (M, K) if shared_x else (E, M, K)
    x = jax.random.normal(kx, shape, jnp.float32)
    for layer in range(L):
        got = jax.jit(stream_matmul)(x, w, jnp.int32(layer))
        assert got.shape == (E, M, N)
        for e in range(E):
            xe = x if shared_x else x[e]
            want = _bf16_dot(xe, w[:, e], layer)
            np.testing.assert_allclose(
                got[e], want, rtol=0,
                atol=1e-5 * float(jnp.abs(want).max()))


def _tiny(name, n_kv_heads, qkv_bias):
    return ModelConfig(name=name, family="dense", n_layers=LAYERS,
                       d_model=256, n_heads=4, n_kv_heads=n_kv_heads,
                       head_dim=64, d_ff=384, vocab_size=512,
                       qkv_bias=qkv_bias)


_DS = get_smoke_config("deepseek-v2-lite-16b")
TINY = {"gqa": _tiny("tiny-gqa", 2, False),
        "mha-bias": _tiny("tiny-mha-bias", 4, True),
        # MLA, a leading dense layer and two layers holding 2 of 4 experts
        "mla-moe": dataclasses.replace(
            _DS, n_layers=LAYERS,
            moe=dataclasses.replace(_DS.moe, n_held=2, first_held=1))}


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
