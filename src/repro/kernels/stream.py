"""Pallas TPU weight-streaming matmul for the decode step's projections.

A decode step multiplies a few rows of activations by every block
weight, so its projections are bound by the bytes of the weights, not
by their operations. XLA gives a default-precision f32 dot bfloat16
operands by converting the weight first; inside the decode loop it
hoists that convert out of the loop and so writes a bfloat16 copy of
every stacked ``(L, K, N)`` weight on every call, and the dots then
read the copy: a step moves twice the weights' fp32 bytes.

``stream_matmul`` reads each weight once, in place in its stacked
array. The layer index is **scalar prefetched** so the weight
``BlockSpec``'s index map picks ``(layer, k, n)`` tiles straight out of
the stack (a per-layer slice handed to a custom call would be
materialised, a read and a write of the weight). Each grid step casts
its x tile and weight tile to bfloat16 in VMEM (round to nearest even,
as XLA's convert does) and accumulates ``dot(..., f32)`` into a VMEM
scratch: the arithmetic of a default-precision f32 dot on the TPU,
bf16 operands and f32 accumulation, without the copy.

An expert stack ``(L, E, K, N)`` is read the same way, every expert of
layer ``layer`` in one call: the grid gains a leading expert axis and
gives ``(E, M, N)``, from one x shared by every expert ``(M, K)`` or one
per expert ``(E, M, K)``.

VMEM per grid step (fp32 storage): the weight tile is the only large
block, double-buffered; x, the output block and the accumulator are
``M`` rows wide. A tile is at most ``TILE_BYTES`` where both dims are
multiples of the 128-wide lane; a dim that is not (576, 10944) is
taken whole, the other as narrow as one lane, and the call's VMEM
limit is raised to what its blocks need.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import resolve_interpret

LANE = 128
# largest weight tile, in bytes at fp32: double-buffered it stays under
# v5e's 16 MiB of scoped VMEM with room for x, output and accumulator
TILE_BYTES = 4 * 2**20
MAX_TILE_N = 2048


def pick_tile(dim: int, cap: int) -> int:
    """Largest multiple of the 128-wide lane that divides ``dim`` and is
    at most ``cap``; the whole ``dim`` if it is no multiple of 128."""
    if dim % LANE:
        return dim
    best = LANE
    for t in range(LANE, min(dim, cap) + 1, LANE):
        if dim % t == 0:
            best = t
    return best


def stream_tiles(K: int, N: int, itemsize: int = 4):
    """(tk, tn) for a (K, N) weight: the output tile as wide as
    ``MAX_TILE_N`` allows, then as many rows as ``TILE_BYTES`` holds. A
    K that is no multiple of the lane is taken whole (x's block spans
    K), with as many output columns as ``TILE_BYTES`` then holds."""
    if K % LANE:
        return K, pick_tile(N, max(LANE, TILE_BYTES // (K * itemsize)))
    tn = pick_tile(N, MAX_TILE_N)
    tk = pick_tile(K, max(LANE, TILE_BYTES // (tn * itemsize)))
    return tk, tn


def vmem_bytes(M: int, tk: int, tn: int, itemsize: int = 4) -> int:
    """VMEM a grid step holds: the weight, x and output blocks double
    buffered, and the f32 accumulator."""
    return 2 * (tk * tn + M * tk + M * tn) * itemsize + M * tn * 4


def _stream_kernel(layer_ref, x_ref, w_ref, o_ref, acc_ref, k_axis=1):
    """One (n, k) step: accumulate x tile @ weight tile; ``k_axis`` is
    the grid axis over K (the last)."""
    k = pl.program_id(k_axis)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...].astype(jnp.bfloat16),
                            w_ref[...].astype(jnp.bfloat16),
                            preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(k_axis) - 1)
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


# scoped VMEM a kernel may take without asking (v5e)
VMEM_DEFAULT = 16 * 2**20


def _compiler_params(semantics, need: int):
    limit = None if need <= VMEM_DEFAULT * 3 // 4 else need + 4 * 2**20
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=limit)


def stream_matmul(x, w_stack, layer, *, interpret=None):
    """``x @ w_stack[layer]`` with bfloat16 operands and f32
    accumulation, reading layer ``layer`` of the stack once and in
    place. x: (M, K); w_stack: (L, K, N); layer: int32 scalar (may be
    traced). Returns (M, N) in x's dtype. For an expert stack
    ``(L, E, K, N)`` and x (M, K) or (E, M, K), every expert's product:
    (E, M, N)."""
    if w_stack.ndim == 4:
        return _stream_experts(x, w_stack, layer, interpret)
    interpret = resolve_interpret(interpret)
    M, K = x.shape
    _, _, N = w_stack.shape
    item = w_stack.dtype.itemsize
    tk, tn = stream_tiles(K, N, item)
    return pl.pallas_call(
        _stream_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(N // tn, K // tk),
            in_specs=[
                pl.BlockSpec((M, tk), lambda n, k, l: (0, k)),
                pl.BlockSpec((None, tk, tn), lambda n, k, l: (l[0], k, n)),
            ],
            out_specs=pl.BlockSpec((M, tn), lambda n, k, l: (0, n)),
            scratch_shapes=[pltpu.VMEM((M, tn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        compiler_params=_compiler_params(("parallel", "arbitrary"),
                                         vmem_bytes(M, tk, tn, item)),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), x, w_stack)


def _stream_experts(x, w_stack, layer, interpret):
    interpret = resolve_interpret(interpret)
    _, E, K, N = w_stack.shape
    M = x.shape[-2]
    item = w_stack.dtype.itemsize
    tk, tn = stream_tiles(K, N, item)
    if x.ndim == 2:             # one x for every expert
        x_spec = pl.BlockSpec((M, tk), lambda e, n, k, l: (0, k))
    else:
        x_spec = pl.BlockSpec((None, M, tk), lambda e, n, k, l: (e, 0, k))
    return pl.pallas_call(
        functools.partial(_stream_kernel, k_axis=2),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(E, N // tn, K // tk),
            in_specs=[
                x_spec,
                pl.BlockSpec((None, None, tk, tn),
                             lambda e, n, k, l: (l[0], e, k, n)),
            ],
            out_specs=pl.BlockSpec((None, M, tn),
                                   lambda e, n, k, l: (e, 0, n)),
            scratch_shapes=[pltpu.VMEM((M, tn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((E, M, N), x.dtype),
        compiler_params=_compiler_params(
            ("parallel", "parallel", "arbitrary"),
            vmem_bytes(M, tk, tn, item)),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), x, w_stack)
