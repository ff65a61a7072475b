"""Compile-only checks of the SGMV kernels for a described TPU v5e chip.

Nothing runs: each test lowers one kernel at a served model's widths for
one chip of a described ``v5e:2x2`` topology and asserts that the TPU
compiler accepted it as a Mosaic kernel (``tpu_custom_call``). That
catches what interpret mode cannot: slices the tiling does not allow,
blocks over the kernel's fast-memory limit, APIs the installed Pallas no
longer has. Widths are fp32 InternLM2-1.8B (d_model 2048; q/o project to
2048, k/v to 1024) and the paper's Llama-7B (d_model 4096), whole for
the single-chip kernels and cut to d/4 for the per-shard halves the
mesh-sharded engine runs at tp=4.

The dense decode step is compiled whole at InternLM2-1.8B and
StableLM-2-1.6B widths and depth, to check that its weight stream
(``kernels/stream.py``) leaves no bf16 copy of the stacked weights and
that its attention (``kernels/decode_attention.py``) reads each layer's
KV in place, with no slice of the cache made.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU compiler's library, and every test
worker imports this file.
"""
import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import kernels
from repro.configs import get_config
from repro.kernels.sgmv import (sgmv_fused_blocks, sgmv_multibank_blocks,
                                sgmv_multibank_expand,
                                sgmv_multibank_shrink)
from repro.models import model as M

WIDTHS = {                       # name -> (d_model, d_out)
    "internlm2-qo": (2048, 2048),
    "internlm2-kv": (2048, 1024),
    "llama7b": (4096, 4096),      # two 2048-wide output blocks
}
RANKS, COUNTS = (8, 64, 128), (2, 1, 1)   # rank buckets, adapters each
BLOCK_T, TOKENS = 16, 64
TP = 4


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # no compiler logs on disk
        # a program compiled for a described chip can be written to the
        # persistent cache but never read back: keep the cache off here
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            try:
                topo = topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2")
            except Exception as e:     # no TPU compiler in this install
                pytest.skip(f"no v5e:2x2 topology can be described: {e}")
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _shapes(sharding, *shapes, dtype=jnp.float32):
    return [jax.ShapeDtypeStruct(s, dtype, sharding=sharding)
            for s in shapes]


def _block_meta(sharding, n_adapters):
    t_pad = TOKENS + n_adapters * BLOCK_T
    nblocks = t_pad // BLOCK_T
    meta = _shapes(sharding, (nblocks,), (nblocks,), dtype=jnp.int32)
    return t_pad, meta


@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_sgmv_fused_blocks_compiles(one_chip, width):
    d, d_out = WIDTHS[width]
    na, r = 4, max(RANKS)
    t_pad, (blk, _) = _block_meta(one_chip, na)
    x, a, b = _shapes(one_chip, (t_pad, d), (na, d, r), (na, r, d_out))
    fn = functools.partial(sgmv_fused_blocks, block_t=BLOCK_T,
                           interpret=False)
    assert "tpu_custom_call" in _compiled_text(fn, x, a, b, blk)


@pytest.mark.parametrize("resident", [False, True],
                         ids=["blocked", "resident"])
@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_sgmv_multibank_blocks_compiles(one_chip, width, resident):
    d, d_out = WIDTHS[width]
    t_pad, (bkt, row) = _block_meta(one_chip, sum(COUNTS))
    (x,) = _shapes(one_chip, (t_pad, d))
    banks = tuple(tuple(_shapes(one_chip, (n, d, r), (n, r, d_out)))
                  for r, n in zip(RANKS, COUNTS))
    fn = functools.partial(sgmv_multibank_blocks, block_t=BLOCK_T,
                           resident=(resident,) * len(RANKS),
                           interpret=False)
    assert "tpu_custom_call" in _compiled_text(fn, x, banks, bkt, row)


@pytest.mark.parametrize("resident", [False, True],
                         ids=["blocked", "resident"])
@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_sgmv_multibank_shard_halves_compile(one_chip, width, resident):
    """The mesh-sharded engine's per-shard shrink (local d/tp slice of
    every A bank) and expand (local d_out/tp slice of every B bank)."""
    d, d_out = (w // TP for w in WIDTHS[width])
    t_pad, (bkt, row) = _block_meta(one_chip, sum(COUNTS))
    x, h = _shapes(one_chip, (t_pad, d), (t_pad, max(RANKS)))
    a_banks = tuple(_shapes(one_chip, *[(n, d, r)
                                        for r, n in zip(RANKS, COUNTS)]))
    b_banks = tuple(_shapes(one_chip, *[(n, r, d_out)
                                        for r, n in zip(RANKS, COUNTS)]))
    res = (resident,) * len(RANKS)
    shrink = functools.partial(sgmv_multibank_shrink, block_t=BLOCK_T,
                               resident=res, interpret=False)
    expand = functools.partial(sgmv_multibank_expand, block_t=BLOCK_T,
                               resident=res, interpret=False)
    assert "tpu_custom_call" in _compiled_text(shrink, x, a_banks, bkt, row)
    assert "tpu_custom_call" in _compiled_text(expand, h, b_banks, bkt, row)


# the served configurations (chipbench/configs), batch 4 over 1024 slots
DECODE = {"internlm2-1.8b": {},
          "stablelm-1.6b": dict(head_dim=64, qkv_bias=True)}


@pytest.mark.parametrize("arch", sorted(DECODE))
def test_decode_step_streams_the_stacked_weights(one_chip, arch,
                                                 monkeypatch):
    """fp32 weights on a TPU take the weight stream: the step holds the
    kernel, no convert writes a bf16 copy of a stacked block weight (the
    XLA path writes seven, 3.0 GB / 2.6 GB of temporaries), and the
    temporaries stay small."""
    cfg = dataclasses.replace(get_config(arch), **DECODE[arch])
    # code that asks for the backend sees the CPU here: steer it to the
    # TPU's branch, as the described chip is one
    monkeypatch.setattr(kernels, "default_interpret", lambda: False)

    def shapes(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = shapes(jax.eval_shape(
        lambda: M.init_params(cfg, jax.random.PRNGKey(0))))
    cache = shapes(jax.eval_shape(lambda: M.init_cache(cfg, 4, 1024)))
    (tokens,) = _shapes(one_chip, (4,), dtype=jnp.int32)
    step = functools.partial(M.decode_step, cfg)
    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, cache, tokens).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    stack_copy = re.compile(rf"= bf16\[{cfg.n_layers},\S* convert\(")
    assert not [line for line in text.splitlines()
                if stack_copy.search(line)]
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5e9


def _padded_bytes(dims, layout):
    """Device bytes of an f32 array of ``dims`` in an HLO ``layout``
    (``3,2,1,0:T(8,128)``): the two most-minor dims rounded up to the
    tile."""
    order, _, tile = layout.partition(":")
    mtm = [int(i) for i in order.split(",")]
    size = [dims[i] for i in mtm]
    if tile:
        t = [int(x) for x in re.findall(r"\d+", tile)[:2]]
        size[1] = -(-size[1] // t[0]) * t[0]
        size[0] = -(-size[0] // t[1]) * t[1]
    n = 4
    for d in size:
        n *= d
    return n


@pytest.mark.parametrize("arch", sorted(DECODE))
def test_decode_step_reads_the_kv_in_place(one_chip, arch, monkeypatch):
    """GQA on a TPU attends through ``decode_attention`` over the
    stacked (L, B, S, Kv*hd) cache: the step holds the kernel, no copy,
    dynamic-slice or dynamic-update-slice makes an f32 array of a
    layer's KV or more (the parent sliced, relaid and wrote back each
    layer's k and v: eight slice-sized passes each), the cache
    arguments take their logical bytes (no padded tile), and no
    temporary is as large as a layer's KV."""
    cfg = dataclasses.replace(get_config(arch), **DECODE[arch])
    monkeypatch.setattr(kernels, "default_interpret", lambda: False)

    def shapes(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    B, S = 4, 1024
    params = shapes(jax.eval_shape(
        lambda: M.init_params(cfg, jax.random.PRNGKey(0))))
    cache = shapes(jax.eval_shape(lambda: M.init_cache(cfg, B, S)))
    (tokens,) = _shapes(one_chip, (B,), dtype=jnp.int32)
    compiled = jax.jit(functools.partial(M.decode_step, cfg),
                       donate_argnums=(1,)).lower(
        params, cache, tokens).compile()
    text = compiled.as_text()
    assert re.search(r"%decode_attention\S* = .*tpu_custom_call", text)
    width = cfg.n_kv_heads * cfg.resolved_head_dim
    layer_kv = B * S * width
    big = []
    for m in re.finditer(r"= f32\[([\d,]+)\]\S* (copy|dynamic-slice|"
                         r"dynamic-update-slice)\(", text):
        n = 1
        for d in m.group(1).split(","):
            n *= int(d)
        if n >= layer_kv:
            big.append(m.group(0))
    assert not big
    dims = [cfg.n_layers, B, S, width]
    args = re.findall(r"%cache__[kv]__\S* = f32\[([\d,]+)\]\{([^}]*)\} "
                      r"parameter", text)
    assert len(args) == 2
    for shape, layout in args:
        assert [int(d) for d in shape.split(",")] == dims
        assert _padded_bytes(dims, layout) == 4 * cfg.n_layers * layer_kv
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * layer_kv


def test_deepseek_decode_streams_every_block_weight(one_chip, monkeypatch):
    """DeepSeek-V2-Lite's decode step as the benchmark serves it (its
    widths, 9 layers with the dense one first, 8 of 64 experts held,
    batch 8 over 1024 slots, the 16-adapter bank padded to rank 128,
    absorbed MLA): every streamed block weight takes the kernel (wq,
    w_dkv, wo and the dense SwiGLU in the dense stack; those, the held
    experts and the shared ones in the expert stack), and no convert
    writes a bf16 copy of a weight, a bank or the cache: none of a
    million elements or more."""
    from repro.lora.bank import build_bank
    monkeypatch.setattr(kernels, "default_interpret", lambda: False)
    base = get_config("deepseek-v2-lite-16b")
    cfg = dataclasses.replace(base, n_layers=9, moe=dataclasses.replace(
        base.moe, n_held=8))
    ranks = dict(enumerate((8,) * 6 + (16,) * 4 + (32,) * 3 + (64,) * 2
                           + (128,)))
    ranks = {f"a{i}": r for i, r in ranks.items()}

    def shapes(fn):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), jax.eval_shape(fn))

    params = shapes(lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
    cache = shapes(lambda: M.init_cache(cfg, 8, 1024))
    bank = shapes(lambda: build_bank(cfg, ranks, jax.random.PRNGKey(1),
                                     n_layers=9).data)
    tokens, idx = _shapes(one_chip, (8,), (8,), dtype=jnp.int32)

    def step(params, cache, tokens, bank, idx):
        return M.decode_step(cfg, params, cache, tokens, bank=bank,
                             lora_idx=idx, mla_absorbed=True)

    text = jax.jit(step, donate_argnums=(1,)).lower(
        params, cache, tokens, bank, idx).compile().as_text()
    assert text.count("tpu_custom_call") == 6 + 9
    # MLA keeps its latent cache and absorbed attention: no GQA kernel
    assert not re.search(r"%decode_attention\S* = ", text)
    big = []
    for m in re.finditer(r"= bf16\[([\d,]+)\]\S* convert\(", text):
        n = 1
        for d in m.group(1).split(","):
            n *= int(d)
        if n >= 2 ** 20:
            big.append(m.group(0))
    assert not big
