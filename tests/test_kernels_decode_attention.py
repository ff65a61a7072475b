"""The decode-attention kernel (``kernels/decode_attention.py``) in
interpret mode: against ``attend_cache`` over the layer gathered from
the stack, and the dense decode step with the kernel path forced on
against the XLA path."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import kernels
from repro.configs import get_smoke_config
from repro.configs.base import ModelConfig
from repro.kernels import decode_attention as DA
from repro.kernels.decode_attention import decode_attention, seq_block
from repro.models import model as M
from repro.models.common import attend_cache, axis_env

L, S, BLOCK = 3, 64, 16
# live lengths: a free slot, one token, a block edge and either side of
# it, and the whole cache
LIVE = (0, 1, BLOCK - 1, BLOCK, BLOCK + 1, S)


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _bf16_attention(q, kc, vc, live):
    """Attention with the kernel's operands: bf16 q, k, v and weights
    (rounded before they are normalised), f32 sums."""
    B, H, hd = q.shape
    Kv = kc.shape[2]
    qf = _bf16(q.reshape(B, Kv, H // Kv, hd) * hd ** -0.5)
    s = jnp.einsum("bkgh,bskh->bkgs", qf, _bf16(kc), precision="highest")
    valid = jnp.arange(kc.shape[1])[None] < live[:, None]
    s = jnp.where(valid[:, None, None], s, -1e30)
    p = jnp.exp(s - s.max(-1, keepdims=True))
    o = jnp.einsum("bkgs,bskh->bkgh", _bf16(p), _bf16(vc),
                   precision="highest") / p.sum(-1)[..., None]
    return o.reshape(B, H, hd)


@pytest.mark.parametrize("layer", [0, L - 1])
@pytest.mark.parametrize("group", [1, 2])
@pytest.mark.parametrize("hd", [64, 128])
def test_decode_attention_matches_attend_cache(hd, group, layer,
                                              monkeypatch):
    Kv = 4
    H, B = Kv * group, len(LIVE)
    monkeypatch.setattr(DA, "BLOCK_BYTES", BLOCK * Kv * hd * 4)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(hd + group), 3)
    q = jax.random.normal(kq, (B, H, hd), jnp.float32)
    k = jax.random.normal(kk, (L, B, S, Kv * hd), jnp.float32)
    v = jax.random.normal(kv, (L, B, S, Kv * hd), jnp.float32)
    live = jnp.asarray(LIVE, jnp.int32)
    got = jax.jit(decode_attention)(q, k, v, jnp.int32(layer), live)
    assert got.shape == (B, H, hd) and got.dtype == jnp.float32
    np.testing.assert_array_equal(got[0], 0.0)          # a free slot
    kc = k[layer].reshape(B, S, Kv, hd)
    vc = v[layer].reshape(B, S, Kv, hd)
    valid = jnp.arange(S)[None] < live[:, None]
    want = attend_cache(q[:, None], kc, vc, valid)[:, 0]
    # f32 attention within the error of bf16 products (2^-8 per operand)
    np.testing.assert_allclose(got[1:], want[1:], rtol=0, atol=3e-2)
    # the same bf16 operands; the kernel rounds each block's weights at
    # that block's running max, so they differ by a weight's rounding
    np.testing.assert_allclose(got[1:], _bf16_attention(q, kc, vc, live)[1:],
                               rtol=0, atol=2e-3)
    # the layer, and each row's tokens, are the ones asked for
    other = attend_cache(q[:, None], k[(layer + 1) % L].reshape(kc.shape),
                         v[(layer + 1) % L].reshape(vc.shape), valid)[:, 0]
    assert float(jnp.abs(got - other)[1:].max()) > 0.1


@pytest.mark.parametrize("S,width,block", [(1024, 2048, 256),
                                           (1024, 1024, 512),
                                           (64, 128, 64), (20, 128, 20)])
def test_seq_block(S, width, block):
    assert seq_block(S, width) == block


def _dense(name, n_kv_heads, head_dim):
    return ModelConfig(name=name, family="dense", n_layers=L, d_model=256,
                       n_heads=4, n_kv_heads=n_kv_heads, head_dim=head_dim,
                       d_ff=384, vocab_size=512, qkv_bias=True)


DENSE = {"mha-64": _dense("tiny-mha", 4, 64),
         "gqa-128": _dense("tiny-gqa", 2, 128)}


def _greedy(cfg, params, steps=6, max_len=32):
    """Logits and tokens of greedy decode steps after a prefill, rows at
    their own positions and one free slot (parked past the cache)."""
    prompt = jnp.arange(1, 9, dtype=jnp.int32)[None].repeat(4, 0) \
        + jnp.arange(4, dtype=jnp.int32)[:, None]
    logits, cache = M.prefill(cfg, params, prompt, cache_len=max_len)
    cache["pos"] = jnp.asarray([8, 3, 5, max_len], jnp.int32)
    step = jax.jit(functools.partial(M.decode_step, cfg))
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    out, toks = [], []
    for _ in range(steps):
        logits, cache = step(params, cache, tok)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        out.append(logits)
        toks.append(tok)
    return jnp.stack(out), jnp.stack(toks)


@pytest.mark.parametrize("arch", sorted(DENSE))
def test_decode_step_attends_through_the_kernel(arch, monkeypatch):
    cfg = DENSE[arch]
    params = M.init_params(cfg, jax.random.PRNGKey(3))
    attn = params["blocks"]["attn"]
    for i, b in enumerate(("bq", "bk", "bv")):
        attn[b] = 0.5 * jax.random.normal(jax.random.PRNGKey(10 + i),
                                          attn[b].shape)
    assert not M.kernel_attention(cfg, 0)           # the CPU keeps XLA's
    xla, xla_toks = _greedy(cfg, params)
    monkeypatch.setattr(M, "kernel_attention", lambda cfg, window: True)
    # 8-token blocks: rows span one to three of the 32 slots' four
    width = cfg.n_kv_heads * cfg.resolved_head_dim
    monkeypatch.setattr(DA, "BLOCK_BYTES", 8 * width * 4)
    got, toks = _greedy(cfg, params)
    assert bool(jnp.isfinite(got).all())
    # the live rows (the free slot reads no block on the kernel path)
    np.testing.assert_array_equal(toks[:, :3], xla_toks[:, :3])
    np.testing.assert_allclose(got[:, :3], xla[:, :3], rtol=0,
                               atol=2e-2 * float(jnp.abs(xla).max()))
    assert float(jnp.abs(got[:, :3] - xla[:, :3]).max()) > 0  # bf16 != f32


_MLA = get_smoke_config("deepseek-v2-lite-16b")
WHO = {"dense": (DENSE["gqa-128"], 0, None, True),
       "moe-gqa": (get_smoke_config("llama4-scout-17b-a16e"), 0, None,
                   True),
       "vlm": (get_smoke_config("llama-3.2-vision-90b"), 0, None, False),
       "window": (DENSE["gqa-128"], 16, None, False),
       "mla": (_MLA, 0, None, False),
       "mesh": (DENSE["gqa-128"], 0, object(), False)}


@pytest.mark.parametrize("case", sorted(WHO))
def test_who_attends_through_the_kernel(case, monkeypatch):
    """On a TPU (as the code sees it): a dense or MoE block's GQA
    without a window and without a mesh takes the kernel; a window,
    MLA, the VLM step and a mesh keep XLA's."""
    cfg, window, mesh, want = WHO[case]
    monkeypatch.setattr(kernels, "default_interpret", lambda: False)
    with axis_env(mesh=mesh):
        assert M.kernel_attention(cfg, window) is want
