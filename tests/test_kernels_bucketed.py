"""Token-compacting ``sgmv_rank_bucketed`` vs the pure-jnp oracle:
mixed-rank batches, compact (per-bucket) banks, the decode case
(block_t=1), and the single-bucket degenerate case."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import sgmv, sgmv_rank_bucketed, sgmv_reference
from repro.kernels.ops import padded_len, prepare_segments_bucketed
from repro.kernels.sgmv import (sgmv_multibank_blocks,
                                sgmv_multibank_expand,
                                sgmv_multibank_shrink)


def _mixed_setup(seed=3, T=29, d=128, do=256, r_small=8, r_big=64):
    """3 adapters in 2 buckets; returns both the full padded bank and the
    per-bucket compact banks holding the same weights."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (T, d))
    A8 = jax.random.normal(ks[1], (2, d, r_small)) * 0.1
    B8 = jax.random.normal(ks[2], (2, r_small, do)) * 0.1
    A64 = jax.random.normal(ks[3], (1, d, r_big)) * 0.1
    B64 = jax.random.normal(ks[4], (1, r_big, do)) * 0.1
    # padded bank: adapters 0,2 are the rank-8 pair, adapter 1 is rank-64
    Apad = jnp.stack([
        jnp.pad(A8[0], ((0, 0), (0, r_big - r_small))), A64[0],
        jnp.pad(A8[1], ((0, 0), (0, r_big - r_small)))])
    Bpad = jnp.stack([
        jnp.pad(B8[0], ((0, r_big - r_small), (0, 0))), B64[0],
        jnp.pad(B8[1], ((0, r_big - r_small), (0, 0)))])
    aid = jax.random.randint(ks[5], (T,), 0, 3)
    bucket = jnp.array([0, 1, 0], jnp.int32)
    local = jnp.array([0, 0, 1], jnp.int32)
    return x, [(A8, B8), (A64, B64)], (Apad, Bpad), aid, bucket, local


@pytest.mark.parametrize("block_t", [16, 8, 1])   # 1 == decode (BGMV)
def test_bucketed_compact_banks_match_reference(block_t):
    x, banks, (Apad, Bpad), aid, bucket, local = _mixed_setup()
    y_b = sgmv_rank_bucketed(x, banks, aid, bucket, adapter_local=local,
                             block_t=block_t, interpret=True)
    y_r = sgmv_reference(x, Apad, Bpad, aid)
    np.testing.assert_allclose(np.asarray(y_b), np.asarray(y_r), atol=1e-4)


def test_bucketed_full_banks_match_reference():
    """adapter_local=None: every bucket bank indexed by the global
    adapter id (the pre-refactor layout) still works."""
    key = jax.random.PRNGKey(2)
    A8 = jax.random.normal(key, (3, 128, 8)) * 0.1
    B8 = jax.random.normal(key, (3, 8, 256)) * 0.1
    A64 = jax.random.normal(key, (3, 128, 64)) * 0.1
    B64 = jax.random.normal(key, (3, 64, 256)) * 0.1
    bucket = jnp.array([0, 1, 0])
    Apad = jnp.where(bucket[:, None, None] == 0,
                     jnp.pad(A8, ((0, 0), (0, 0), (0, 56))), A64)
    Bpad = jnp.where(bucket[:, None, None] == 0,
                     jnp.pad(B8, ((0, 0), (0, 56), (0, 0))), B64)
    x = jax.random.normal(key, (24, 128))
    aid = jax.random.randint(key, (24,), 0, 3)
    y_b = sgmv_rank_bucketed(x, [(A8, B8), (A64, B64)], aid, bucket,
                             interpret=True)
    y_r = sgmv_reference(x, Apad, Bpad, aid)
    np.testing.assert_allclose(np.asarray(y_b), np.asarray(y_r), atol=1e-4)


def test_single_bucket_degenerates_to_sgmv():
    """One bucket == plain SGMV on the same bank (no splitting overhead
    in the math)."""
    key = jax.random.PRNGKey(5)
    ks = jax.random.split(key, 4)
    x = jax.random.normal(ks[0], (17, 64))
    A = jax.random.normal(ks[1], (2, 64, 16)) * 0.1
    B = jax.random.normal(ks[2], (2, 16, 128)) * 0.1
    aid = jax.random.randint(ks[3], (17,), 0, 2)
    bucket = jnp.zeros((2,), jnp.int32)
    local = jnp.arange(2, dtype=jnp.int32)
    y_b = sgmv_rank_bucketed(x, [(A, B)], aid, bucket,
                             adapter_local=local, interpret=True)
    y_s = sgmv(x, A, B, aid, interpret=True)
    np.testing.assert_allclose(np.asarray(y_b), np.asarray(y_s), atol=1e-5)


def test_empty_bucket_is_skipped():
    """A bucket with no tokens in the batch contributes nothing (and the
    kernel for it never launches)."""
    x, banks, (Apad, Bpad), _, bucket, local = _mixed_setup()
    aid = jnp.full((x.shape[0],), 1, jnp.int32)   # only the rank-64 one
    y_b = sgmv_rank_bucketed(x, banks, aid, bucket, adapter_local=local,
                             interpret=True)
    y_r = sgmv_reference(x, Apad, Bpad, aid)
    np.testing.assert_allclose(np.asarray(y_b), np.asarray(y_r), atol=1e-4)


def test_scaling_applied_bucketed():
    x, banks, _, aid, bucket, local = _mixed_setup()
    y1 = sgmv_rank_bucketed(x, banks, aid, bucket, adapter_local=local,
                            scaling=2.0, interpret=True)
    y2 = sgmv_rank_bucketed(x, banks, aid, bucket, adapter_local=local,
                            scaling=1.0, interpret=True)
    np.testing.assert_allclose(np.asarray(y1), 2 * np.asarray(y2),
                               rtol=1e-5)


@pytest.mark.parametrize("resident", [(True, True), (False, True)])
@pytest.mark.parametrize("kernel", ["fused", "split"])
def test_multibank_resident_bank_over_several_output_blocks(kernel,
                                                            resident):
    """A resident bucket holds its whole (Na, r, d_out) B bank, so with
    block_o < d_out each output block slices its own columns out of the
    bank row: the fused multibank kernel and the split expand kernel
    (the mesh-sharded path) must both still match the oracle."""
    x, banks, (Apad, Bpad), aid, bucket, local = _mixed_setup()
    T, d = x.shape
    bt, bo = 8, 128                      # d_out=256: two output blocks
    Na = bucket.shape[0]
    dest, blk = prepare_segments_bucketed(aid, bucket, Na, len(banks), bt)
    x_pad = jnp.zeros((padded_len(T, Na, bt), d), x.dtype).at[dest].set(x)
    bkt, row = bucket[blk], local[blk]
    if kernel == "fused":
        y_pad = sgmv_multibank_blocks(x_pad, tuple(banks), bkt, row,
                                      block_t=bt, block_o=bo,
                                      resident=resident, interpret=True)
    else:
        h = sgmv_multibank_shrink(x_pad, tuple(A for A, _ in banks), bkt,
                                  row, block_t=bt, resident=resident,
                                  interpret=True)
        y_pad = sgmv_multibank_expand(h, tuple(B for _, B in banks), bkt,
                                      row, block_t=bt, block_o=bo,
                                      resident=resident, interpret=True)
    y_r = sgmv_reference(x, Apad, Bpad, aid)
    np.testing.assert_allclose(np.asarray(y_pad[dest]), np.asarray(y_r),
                               atol=1e-4)
