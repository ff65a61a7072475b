"""Pallas TPU SGMV kernels (shrink + expand), the TPU-native adaptation of
Punica's segmented-gather GEMM (DESIGN.md §3).

Layout contract (established by ``ops.prepare_segments``): tokens are
sorted by adapter and padded so each adapter's segment occupies whole
``block_t``-row blocks. The per-block adapter id array is **scalar
prefetched** — ``BlockSpec.index_map`` reads it to gather the right A/B
slice from the HBM-resident bank into VMEM, so each grid step runs a
dense (block_t × d) × (d × r) MXU matmul with zero gather overhead in the
inner loop. Everything is padded to the bank max rank — faithfully
reproducing the max-rank tax of BGMV/MBGMV batches.

VMEM budget per grid step (fp32):
  shrink: block_t*d + d*r + block_t*r       (d=8192, r=128: ~4.3 MB)
  expand: block_t*r + r*block_o + block_t*block_o (block_o=2048: ~1.3 MB)
Both well under the ~16 MB/core VMEM of TPU v5e; block shapes keep the
MXU dims at multiples of 128 where the model dims allow. Caveat found
by ``repro.analysis.vmem``: the multibank kernel double-buffers every
bucket's A/B blocks, so a full 5-bucket bank set at d=8192 fits the
budget only at bf16 (~11 MB) — fp32 (~20 MB) is over it, which is fine
for the CPU interpret-mode paths (no VMEM there) but means compiled
TPU runs must use bf16 banks or fewer co-dispatched buckets.

``sgmv_fused_blocks`` fuses the pair: one grid sweep computes the
(block_t, r) shrink product into a VMEM scratch at the first output
block of each token block and expands it over the output blocks while it
is still resident — the rank-r intermediate never round-trips HBM and
the dispatch count halves. ``sgmv_multibank_blocks`` generalizes that to
a whole rank-bucketed bank set in ONE dispatch: per-block scalar-
prefetched (bucket, bank-row) metadata steers each token block to its
own bucket's A/B pair, and the kernel body branches (``pl.when``) to a
dot at that bucket's OWN rank, so a rank-8 block pays rank-8 compute
co-dispatched with rank-128 blocks. Non-matching buckets' index maps
clamp to row 0 — with the bucket-major token layout consecutive grid
steps then re-request the same block and the pipeline elides the fetch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import resolve_interpret


def _shrink_kernel(aid_ref, x_ref, a_ref, o_ref):
    x = x_ref[...]                                   # (bt, d)
    a = a_ref[0]                                     # (d, r)
    o_ref[...] = jnp.dot(
        x, a, preferred_element_type=jnp.float32).astype(o_ref.dtype)


def _expand_kernel(aid_ref, h_ref, b_ref, o_ref):
    h = h_ref[...]                                   # (bt, r)
    b = b_ref[0]                                     # (r, bo)
    o_ref[...] = jnp.dot(
        h, b, preferred_element_type=jnp.float32).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def sgmv_shrink(x_pad, A, block_adapter, *, block_t: int = 16,
                interpret=None):
    """x_pad: (T_pad, d) segment-blocked; A: (Na, d, r);
    block_adapter: (nblocks,) int32. Returns (T_pad, r)."""
    interpret = resolve_interpret(interpret)
    T_pad, d = x_pad.shape
    Na, _, r = A.shape
    nblocks = T_pad // block_t
    return pl.pallas_call(
        _shrink_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nblocks,),
            in_specs=[
                pl.BlockSpec((block_t, d), lambda i, aid: (i, 0)),
                pl.BlockSpec((1, d, r), lambda i, aid: (aid[i], 0, 0)),
            ],
            out_specs=pl.BlockSpec((block_t, r), lambda i, aid: (i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((T_pad, r), x_pad.dtype),
        interpret=interpret,
    )(block_adapter, x_pad, A)


@functools.partial(jax.jit,
                   static_argnames=("block_t", "block_o", "interpret"))
def sgmv_expand(h_pad, B, block_adapter, *, block_t: int = 16,
                block_o: int = 2048, interpret=None):
    """h_pad: (T_pad, r); B: (Na, r, d_out). Returns (T_pad, d_out)."""
    interpret = resolve_interpret(interpret)
    T_pad, r = h_pad.shape
    Na, _, d_out = B.shape
    bo = min(block_o, d_out)
    # pad d_out to a multiple of bo
    pad_o = (-d_out) % bo
    Bp = jnp.pad(B, ((0, 0), (0, 0), (0, pad_o)))
    n_ob = (d_out + pad_o) // bo
    nblocks = T_pad // block_t
    out = pl.pallas_call(
        _expand_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nblocks, n_ob),
            in_specs=[
                pl.BlockSpec((block_t, r), lambda i, j, aid: (i, 0)),
                pl.BlockSpec((1, r, bo), lambda i, j, aid: (aid[i], 0, j)),
            ],
            out_specs=pl.BlockSpec((block_t, bo),
                                   lambda i, j, aid: (i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((T_pad, d_out + pad_o), h_pad.dtype),
        interpret=interpret,
    )(block_adapter, h_pad, Bp)
    return out[:, :d_out]


# ---------------------------------------------------------------------------
# Fused shrink+expand
# ---------------------------------------------------------------------------


def _fused_kernel(aid_ref, x_ref, a_ref, b_ref, o_ref, h_ref):
    # j (output-block dim) is the innermost grid dim: the shrink product
    # is computed once per token block (j == 0) into VMEM scratch and
    # stays resident for every output block — no HBM round-trip. The
    # scratch holds x.dtype, mirroring the unfused path's inter-kernel
    # cast so fused and unfused outputs are bit-identical.
    @pl.when(pl.program_id(1) == 0)
    def _():
        h_ref[...] = jnp.dot(
            x_ref[...], a_ref[0],
            preferred_element_type=jnp.float32).astype(h_ref.dtype)

    o_ref[...] = jnp.dot(
        h_ref[...], b_ref[0],
        preferred_element_type=jnp.float32).astype(o_ref.dtype)


def _fused_kernel_1ob(aid_ref, x_ref, a_ref, b_ref, o_ref):
    # single-output-block specialization (d_out <= block_o): the shrink
    # product lives in registers only — no scratch, no conditional
    h = jnp.dot(x_ref[...], a_ref[0],
                preferred_element_type=jnp.float32).astype(x_ref.dtype)
    o_ref[...] = jnp.dot(h, b_ref[0],
                         preferred_element_type=jnp.float32
                         ).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_t", "block_o", "interpret"))
def sgmv_fused_blocks(x_pad, A, B, block_adapter, *, block_t: int = 16,
                      block_o: int = 2048, interpret=None):
    """Fused shrink+expand over a segment-blocked layout: one dispatch,
    (block_t, r) intermediate kept in VMEM. Returns (T_pad, d_out)."""
    interpret = resolve_interpret(interpret)
    T_pad, d = x_pad.shape
    Na, _, r = A.shape
    d_out = B.shape[-1]
    bo = min(block_o, d_out)
    pad_o = (-d_out) % bo
    Bp = jnp.pad(B, ((0, 0), (0, 0), (0, pad_o)))
    n_ob = (d_out + pad_o) // bo
    nblocks = T_pad // block_t
    out = pl.pallas_call(
        _fused_kernel if n_ob > 1 else _fused_kernel_1ob,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nblocks, n_ob),
            in_specs=[
                pl.BlockSpec((block_t, d), lambda i, j, aid: (i, 0)),
                pl.BlockSpec((1, d, r), lambda i, j, aid: (aid[i], 0, 0)),
                pl.BlockSpec((1, r, bo), lambda i, j, aid: (aid[i], 0, j)),
            ],
            out_specs=pl.BlockSpec((block_t, bo), lambda i, j, aid: (i, j)),
            scratch_shapes=[] if n_ob == 1 else
            [pltpu.VMEM((block_t, r), x_pad.dtype)],
        ),
        out_shape=jax.ShapeDtypeStruct((T_pad, d_out + pad_o), x_pad.dtype),
        interpret=interpret,
    )(block_adapter, x_pad, A, Bp)
    return out[:, :d_out]


# ---------------------------------------------------------------------------
# Fused multi-bank (rank-bucketed) kernel: ONE dispatch for all buckets
# ---------------------------------------------------------------------------


def _make_multibank_kernel(bucket_ranks, n_ob, resident, block_o):
    """Kernel factory closed over the static per-bucket ranks. The body
    branches on the block's scalar-prefetched bucket id; only the
    matching branch's dots execute, at that bucket's OWN rank — the
    rank-aware FLOP profile of the host-loop dispatcher, without the
    host loop. With one output block the shrink product stays in
    registers; otherwise it parks in VMEM scratch across the j sweep.

    ``resident[b]`` buckets pass their WHOLE bank as the operand block
    (constant index map — fetched once, see ``sgmv_multibank_blocks``),
    so the kernel indexes the bank row itself; blocked buckets get the
    per-row (1, d, r)/(1, r, bo) slice the index map already gathered.
    """
    nb = len(bucket_ranks)

    def kernel_1ob(bkt_ref, row_ref, x_ref, *refs):
        o_ref = refs[2 * nb]
        i = pl.program_id(0)
        bkt = bkt_ref[i]
        row = row_ref[i]
        for b, r_b in enumerate(bucket_ranks):
            a_ref, b_ref = refs[2 * b], refs[2 * b + 1]

            @pl.when(bkt == b)
            def _(a_ref=a_ref, b_ref=b_ref, res=resident[b]):
                a = a_ref[row] if res else a_ref[0]
                bmat = b_ref[row] if res else b_ref[0]
                h = jnp.dot(x_ref[...], a,
                            preferred_element_type=jnp.float32
                            ).astype(x_ref.dtype)
                o_ref[...] = jnp.dot(h, bmat,
                                     preferred_element_type=jnp.float32
                                     ).astype(o_ref.dtype)

    def kernel(bkt_ref, row_ref, x_ref, *refs):
        o_ref, h_ref = refs[2 * nb], refs[2 * nb + 1]
        i, j = pl.program_id(0), pl.program_id(1)
        bkt = bkt_ref[i]
        row = row_ref[i]
        for b, r_b in enumerate(bucket_ranks):
            a_ref, b_ref = refs[2 * b], refs[2 * b + 1]

            @pl.when((bkt == b) & (j == 0))
            def _(a_ref=a_ref, r_b=r_b, res=resident[b]):
                a = a_ref[row] if res else a_ref[0]
                h_ref[:, :r_b] = jnp.dot(
                    x_ref[...], a,
                    preferred_element_type=jnp.float32).astype(h_ref.dtype)

            @pl.when(bkt == b)
            def _(b_ref=b_ref, r_b=r_b, res=resident[b]):
                bmat = (b_ref[row, :, pl.ds(j * block_o, block_o)] if res
                        else b_ref[0])
                o_ref[...] = jnp.dot(
                    h_ref[:, :r_b], bmat,
                    preferred_element_type=jnp.float32).astype(o_ref.dtype)

    return kernel_1ob if n_ob == 1 else kernel


def _bank_a_map(b):
    # non-matching buckets clamp to row 0: consecutive grid steps (the
    # layout is bucket-major) then request the same block and the fetch
    # is elided by the pipeline.
    return lambda i, j, bkt, row: (jnp.where(bkt[i] == b, row[i], 0), 0, 0)


def _bank_b_map(b):
    return lambda i, j, bkt, row: (jnp.where(bkt[i] == b, row[i], 0), 0, j)


def _resident_map(ndim):
    # whole-bank operand: the index map is constant, so every grid step
    # requests block (0, ..., 0) — the pipeline's revisiting
    # optimization fetches it exactly ONCE (XLA hoists the
    # loop-invariant slice in interpret mode), instead of re-fetching a
    # per-row slice on every step like the blocked maps above.
    return lambda *_: (0,) * ndim


@functools.partial(jax.jit,
                   static_argnames=("block_t", "block_o", "resident",
                                    "interpret"))
def sgmv_multibank_blocks(x_pad, banks, block_bucket, block_row, *,
                          block_t: int = 16, block_o: int = 2048,
                          resident=None, interpret=None):
    """One traced dispatch over a whole rank-bucketed bank set.

    x_pad: (T_pad, d) bucket-major segment-blocked tokens; banks: tuple
    of (A_b (Na_b, d, r_b), B_b (Na_b, r_b, d_out)) pairs in ascending
    bucket order; block_bucket/block_row: (nblocks,) int32 scalar-
    prefetched metadata (which bucket, which row of that bucket's bank).
    Returns (T_pad, d_out).

    resident: optional per-bucket bool tuple (from
    ``kernels.tune.block_plan``). A resident bucket's whole A/B bank is
    the operand block with a CONSTANT index map — fetched once for the
    entire sweep instead of a per-row slice per step. That single fetch
    is what fixes the rank-skew regression: with per-row blocked maps,
    every one of the mostly-low-rank grid steps still re-fetched the
    high-rank bucket's (d, r)/(r, d_out) slices."""
    interpret = resolve_interpret(interpret)
    T_pad, d = x_pad.shape
    d_out = banks[0][1].shape[-1]
    ranks = tuple(A.shape[-1] for A, _ in banks)
    if resident is None:
        resident = tuple(False for _ in banks)
    bo = min(block_o, d_out)
    pad_o = (-d_out) % bo
    n_ob = (d_out + pad_o) // bo
    nblocks = T_pad // block_t
    in_specs = [pl.BlockSpec((block_t, d), lambda i, j, bkt, row: (i, 0))]
    operands = [x_pad]
    for b, (A, B) in enumerate(banks):
        Bp = jnp.pad(B, ((0, 0), (0, 0), (0, pad_o)))
        if resident[b]:
            in_specs.append(pl.BlockSpec(A.shape, _resident_map(3)))
            in_specs.append(pl.BlockSpec(Bp.shape, _resident_map(3)))
        else:
            in_specs.append(pl.BlockSpec((1, d, ranks[b]), _bank_a_map(b)))
            in_specs.append(pl.BlockSpec((1, ranks[b], bo),
                                         _bank_b_map(b)))
        operands.extend([A, Bp])
    out = pl.pallas_call(
        _make_multibank_kernel(ranks, n_ob, resident, bo),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nblocks, n_ob),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((block_t, bo),
                                   lambda i, j, bkt, row: (i, j)),
            scratch_shapes=[] if n_ob == 1 else
            [pltpu.VMEM((block_t, max(ranks)), x_pad.dtype)],
        ),
        out_shape=jax.ShapeDtypeStruct((T_pad, d_out + pad_o), x_pad.dtype),
        interpret=interpret,
    )(block_bucket, block_row, *operands)
    return out[:, :d_out]


# ---------------------------------------------------------------------------
# Split multibank shrink / expand: the sharded per-shard reduction contract
# ---------------------------------------------------------------------------
#
# Per-shard reduction contract (mesh-sharded serving): with the LoRA bank
# co-sharded along the model axis — A sharded on d_model (each of the s
# model shards holds a (Na, d/s, r) slice) and B sharded on d_out (each
# holds (Na, r, d_out/s)) — the fused kernel cannot run as one dispatch
# because the rank-r intermediate must be summed ACROSS shards between
# the two dots. The sharded engine therefore runs, inside one shard_map:
#
#     h_local = sgmv_multibank_shrink(x_pad_local_d, A_shard, ...)
#     h       = lax.psum(h_local, "model")     # ONE (T_pad, max_r) psum
#     out     = sgmv_multibank_expand(h, B_shard, ...)
#
# Each shard's kernels see only their local d/s (shrink) and d_out/s
# (expand) slices; the only cross-chip traffic is the rank-r
# intermediate — never the full weights, activations, or the gathered
# bank (S-LoRA's partitioned LoRA computation strategy). The expand
# output is already sharded the same way as the base layer's column-
# parallel projection output, so the delta adds in with no extra
# collective. At tp=1 the pair is bit-identical to the fused kernel
# (same dots, same inter-dot cast); under tp>1 the psum reassociates the
# d-dim sum, so parity with the single-device engine is at token level
# (argmax), not bitwise.


def _make_multibank_shrink_kernel(bucket_ranks, resident):
    nb = len(bucket_ranks)

    def kernel(bkt_ref, row_ref, x_ref, *refs):
        o_ref = refs[nb]
        i = pl.program_id(0)
        bkt = bkt_ref[i]
        row = row_ref[i]
        # zero-fill so columns above the block's own rank are defined
        # (they participate in the cross-shard psum)
        o_ref[...] = jnp.zeros_like(o_ref)
        for b, r_b in enumerate(bucket_ranks):
            a_ref = refs[b]

            @pl.when(bkt == b)
            def _(a_ref=a_ref, r_b=r_b, res=resident[b]):
                a = a_ref[row] if res else a_ref[0]
                o_ref[:, :r_b] = jnp.dot(
                    x_ref[...], a,
                    preferred_element_type=jnp.float32).astype(o_ref.dtype)

    return kernel


@functools.partial(jax.jit,
                   static_argnames=("block_t", "resident", "interpret"))
def sgmv_multibank_shrink(x_pad, A_banks, block_bucket, block_row, *,
                          block_t: int = 16, resident=None,
                          interpret=None):
    """Shrink half of the multibank dispatch: x_pad (T_pad, d_local) x
    per-bucket A (Na_b, d_local, r_b) -> (T_pad, max_r), columns above a
    block's own bucket rank zero-filled. ``d_local`` may be a model-
    sharded slice — see the per-shard reduction contract above."""
    interpret = resolve_interpret(interpret)
    T_pad, d = x_pad.shape
    ranks = tuple(A.shape[-1] for A in A_banks)
    if resident is None:
        resident = tuple(False for _ in A_banks)
    max_r = max(ranks)
    nblocks = T_pad // block_t
    in_specs = [pl.BlockSpec((block_t, d), lambda i, bkt, row: (i, 0))]
    operands = [x_pad]
    for b, A in enumerate(A_banks):
        if resident[b]:
            in_specs.append(pl.BlockSpec(A.shape, _resident_map(3)))
        else:
            in_specs.append(pl.BlockSpec(
                (1, d, ranks[b]),
                lambda i, bkt, row, b=b: (jnp.where(bkt[i] == b,
                                                    row[i], 0), 0, 0)))
        operands.append(A)
    return pl.pallas_call(
        _make_multibank_shrink_kernel(ranks, resident),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nblocks,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((block_t, max_r),
                                   lambda i, bkt, row: (i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((T_pad, max_r), x_pad.dtype),
        interpret=interpret,
    )(block_bucket, block_row, *operands)


def _make_multibank_expand_kernel(bucket_ranks, n_ob, resident, block_o):
    nb = len(bucket_ranks)

    def kernel(bkt_ref, row_ref, h_ref, *refs):
        o_ref = refs[nb]
        i = pl.program_id(0)
        j = pl.program_id(1) if n_ob > 1 else 0
        bkt = bkt_ref[i]
        row = row_ref[i]
        for b, r_b in enumerate(bucket_ranks):
            b_ref = refs[b]

            @pl.when(bkt == b)
            def _(b_ref=b_ref, r_b=r_b, res=resident[b]):
                bmat = (b_ref[row, :, pl.ds(j * block_o, block_o)] if res
                        else b_ref[0])
                o_ref[...] = jnp.dot(
                    h_ref[:, :r_b], bmat,
                    preferred_element_type=jnp.float32).astype(o_ref.dtype)

    return kernel


@functools.partial(jax.jit,
                   static_argnames=("block_t", "block_o", "resident",
                                    "interpret"))
def sgmv_multibank_expand(h_pad, B_banks, block_bucket, block_row, *,
                          block_t: int = 16, block_o: int = 2048,
                          resident=None, interpret=None):
    """Expand half of the multibank dispatch: h_pad (T_pad, max_r)
    (typically the psum of per-shard shrink outputs) x per-bucket B
    (Na_b, r_b, d_out_local) -> (T_pad, d_out_local)."""
    interpret = resolve_interpret(interpret)
    T_pad, max_r = h_pad.shape
    d_out = B_banks[0].shape[-1]
    ranks = tuple(B.shape[1] for B in B_banks)
    if resident is None:
        resident = tuple(False for _ in B_banks)
    bo = min(block_o, d_out)
    pad_o = (-d_out) % bo
    n_ob = (d_out + pad_o) // bo
    nblocks = T_pad // block_t
    in_specs = [pl.BlockSpec((block_t, max_r),
                             lambda i, j, bkt, row: (i, 0))]
    operands = [h_pad]
    for b, B in enumerate(B_banks):
        Bp = jnp.pad(B, ((0, 0), (0, 0), (0, pad_o)))
        if resident[b]:
            in_specs.append(pl.BlockSpec(Bp.shape, _resident_map(3)))
        else:
            in_specs.append(pl.BlockSpec(
                (1, ranks[b], bo),
                lambda i, j, bkt, row, b=b: (jnp.where(bkt[i] == b,
                                                       row[i], 0), 0, j)))
        operands.append(Bp)
    out = pl.pallas_call(
        _make_multibank_expand_kernel(ranks, n_ob, resident, bo),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nblocks, n_ob),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((block_t, bo),
                                   lambda i, j, bkt, row: (i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((T_pad, d_out + pad_o), h_pad.dtype),
        interpret=interpret,
    )(block_bucket, block_row, *operands)
    return out[:, :d_out]
