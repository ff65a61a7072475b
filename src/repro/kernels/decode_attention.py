"""Pallas TPU decode attention over the stacked KV cache, read in place.

A decode step attends one query per row over the keys and values that
row holds in the cache. The cache is stacked over layers as
``(L, B, S, Kv*hd)``: a token of a layer is one lane-dense line holding
every KV head side by side, so the cache needs no padding whatever the
head width, and the new token is one contiguous line.

``decode_attention`` takes the whole stacked k and v caches and a
**scalar-prefetched** layer index, as ``stream.stream_matmul`` takes a
weight stack, so no layer slice is materialised. Each row's live length
(its tokens in the cache; 0 for a free slot) is prefetched too. The
grid runs over (row, sequence block); the k/v index maps clamp the
block to the row's last live one, so no copy is made of the blocks
past it (Pallas fetches nothing when a block index repeats) and
``pl.when`` skips their compute.

Heads without a relayout: the G = H/Kv query heads of each KV head are
laid out **block-diagonally** in a query matrix ``(H, Kv*hd)`` whose
row ``j*Kv + g`` holds query head ``g*G + j`` in the hd columns of KV
head g and zeros elsewhere. A block's scores are then ``Q @ k_blk^T``
``(H, block)``, and ``P @ v_blk`` ``(H, Kv*hd)`` holds each head's
output in its own KV head's columns. At the end the other columns are
masked and the Kv rows of each j summed: ``(G, Kv*hd)``, head
``g*G + j`` at columns ``g*hd..``, an exact sum with one nonzero term.

Products are those of a default-precision f32 dot on the TPU: bf16
operands (the cache is read in its storage dtype and cast in VMEM),
f32 accumulation; the online softmax keeps its running max, sum and
output in f32 VMEM scratch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import resolve_interpret
from .stream import _compiler_params

SUBLANE = 8
# largest k (or v) block in bytes: double-buffered, both stay within
# v5e's 16 MiB of scoped VMEM with room for their bf16 casts
BLOCK_BYTES = 2 * 2**20
NEG_INF = -1e30


def seq_block(S: int, width: int, itemsize: int = 4) -> int:
    """Tokens per sequence block: the largest multiple of the 8-row
    sublane that divides ``S`` and keeps a ``(block, width)`` block
    within ``BLOCK_BYTES``; the whole ``S`` if no multiple of 8 does."""
    if S % SUBLANE:
        return S
    best = SUBLANE
    for t in range(SUBLANE, S + 1, SUBLANE):
        if S % t == 0 and t * width * itemsize <= BLOCK_BYTES:
            best = t
    return best


def vmem_bytes(H: int, G: int, width: int, block: int,
               itemsize: int = 4) -> int:
    """VMEM a grid step holds: the k and v blocks double-buffered and
    their bf16 casts, the bf16 query and the output block
    double-buffered, the f32 accumulator, scores and softmax state."""
    return (4 * block * width * itemsize + 2 * block * width * 2
            + 2 * H * width * 2 + 2 * G * width * 4 + H * width * 4
            + 2 * H * block * 4 + 2 * H * 128 * 4)


def _kernel(layer_ref, lens_ref, q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref, *, block, kv_heads, hd):
    del layer_ref                       # read by the index maps only
    b, j = pl.program_id(0), pl.program_id(1)
    n = lens_ref[b]

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j * block < n)
    def _():
        k = k_ref[...].astype(jnp.bfloat16)
        s = jax.lax.dot_general(q_ref[...], k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        t = j * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(t < n, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + p.sum(axis=1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
            p.astype(jnp.bfloat16), v_ref[...].astype(jnp.bfloat16),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        l = l_ref[...]
        out = acc_ref[...] / jnp.where(l > 0, l, 1.0)   # a free row: 0
        width = out.shape[1]
        g = jax.lax.broadcasted_iota(jnp.int32, (kv_heads, width), 0)
        c = jax.lax.broadcasted_iota(jnp.int32, (kv_heads, width), 1)
        own = (c >= g * hd) & (c < g * hd + hd)
        for r in range(o_ref.shape[0]):
            rows = out[r * kv_heads:(r + 1) * kv_heads]
            o_ref[r:r + 1, :] = jnp.where(own, rows, 0.0).sum(
                axis=0, keepdims=True).astype(o_ref.dtype)


def decode_attention(q, k_stack, v_stack, layer, lens, *, interpret=None):
    """Attention of one query per row over layer ``layer`` of the
    stacked caches, each row over its first ``lens[b]`` tokens.

    q: (B, H, hd); k_stack, v_stack: (L, B, S, Kv*hd); layer: int32
    scalar (may be traced); lens: (B,) int32, 0 for a free row (whose
    output is zeros). Scores are scaled by 1/sqrt(hd). Returns
    (B, H, hd) in q's dtype."""
    interpret = resolve_interpret(interpret)
    B, H, hd = q.shape
    _, _, S, width = k_stack.shape
    Kv = width // hd
    G = H // Kv
    # the block-diagonal query, rows j-major: row j*Kv + g is head
    # g*G + j in KV head g's columns
    qt = (q.astype(jnp.float32) * hd ** -0.5).reshape(B, Kv, G, hd)
    qt = qt.transpose(0, 2, 1, 3)[:, :, :, None, :] \
        * jnp.eye(Kv, dtype=jnp.float32)[:, :, None]
    q_bd = qt.reshape(B, H, width).astype(jnp.bfloat16)
    block = seq_block(S, width, k_stack.dtype.itemsize)

    def kv_map(b, j, layer, lens):
        last = jnp.maximum((lens[b] + block - 1) // block - 1, 0)
        return layer[0], b, jnp.minimum(j, last), 0

    kv_spec = pl.BlockSpec((None, None, block, width), kv_map)
    out = pl.pallas_call(
        functools.partial(_kernel, block=block, kv_heads=Kv, hd=hd),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, S // block),
            in_specs=[
                pl.BlockSpec((None, H, width), lambda b, j, l, n: (b, 0, 0)),
                kv_spec, kv_spec,
            ],
            out_specs=pl.BlockSpec((None, G, width),
                                   lambda b, j, l, n: (b, 0, 0)),
            scratch_shapes=[pltpu.VMEM((H, 1), jnp.float32),
                            pltpu.VMEM((H, 1), jnp.float32),
                            pltpu.VMEM((H, width), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((B, G, width), q.dtype),
        compiler_params=_compiler_params(
            ("parallel", "arbitrary"),
            vmem_bytes(H, G, width, block, k_stack.dtype.itemsize)),
        interpret=interpret,
        name="decode_attention",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), lens.astype(jnp.int32),
      q_bd, k_stack, v_stack)
    # (B, G, Kv, hd) -> head g*G + j
    return out.reshape(B, G, Kv, hd).transpose(0, 2, 1, 3).reshape(B, H, hd)
