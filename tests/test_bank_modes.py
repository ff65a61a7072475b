"""Rank-bucketed LoRA banks end-to-end: padded-vs-bucketed parity on the
real engine (token-identical outputs, allclose logits), the bucketed
cost-model primitives (strictly cheaper for mixed-rank batches), the
Pallas dispatch helper, and the simulated servers' bucketed iteration
costs."""
import copy
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cluster import NetworkModel, ServerModel
from repro.configs import get_smoke_config
from repro.lora import LoRABank, apply_bank_sgmv, build_bank, rank_bucket
from repro.models import model as M
from repro.serving import (LoRAServeCluster, Request, ServingEngine,
                           SimBackend)
from repro.traces import make_adapters, synth_trace

ADAPTERS = {"a-r8": 8, "b-r64": 64, "c-r8": 8}


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("llama-7b-paper")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


# -- bank construction ----------------------------------------------------
@pytest.mark.parametrize("mode", ["padded", "bucketed"])
def test_bank_rank_above_projection_width(mode):
    """A rank above a projection's output width (rank 128 on the smoke
    InternLM2's 64-wide k/v) still pads A along its rank axis and B
    along its rank axis, never the other way round."""
    cfg = get_smoke_config("internlm2-1.8b")
    kv_out = cfg.n_kv_heads * cfg.resolved_head_dim
    assert kv_out < 128
    bank = build_bank(cfg, {"a-r8": 8, "b-r128": 128},
                      jax.random.PRNGKey(0), mode=mode)
    top = bank.data if mode == "padded" else bank.data[-1]
    assert top["k"]["A"].shape[-2:] == (cfg.d_model, 128)
    assert top["k"]["B"].shape[-2:] == (128, kv_out)


def test_rank_bucket_power_of_two():
    assert [rank_bucket(r) for r in (1, 2, 5, 8, 9, 64, 100, 128)] == \
        [1, 2, 8, 8, 16, 64, 128, 128]
    with pytest.raises(ValueError):
        rank_bucket(0)


def test_build_bank_layouts(setup):
    cfg, _ = setup
    key = jax.random.PRNGKey(1)
    pb = build_bank(cfg, ADAPTERS, key, mode="padded")
    bb = build_bank(cfg, ADAPTERS, key, mode="bucketed")
    assert isinstance(pb, LoRABank) and isinstance(bb, LoRABank)
    assert pb.adapter_ids == bb.adapter_ids
    assert pb.max_rank == bb.max_rank == 64
    assert pb.signature[0] == "padded"
    assert bb.signature == ("bucketed", ((8, 2), (64, 1)))
    # padded: one bank at max rank; bucketed: per-bucket banks at own rank
    assert pb.data["q"]["A"].shape[-1] == 64
    assert bb.data[0]["q"]["A"].shape[-1] == 8
    assert bb.data[1]["q"]["A"].shape[-1] == 64
    # bucketed holds strictly fewer parameters than max-rank padding
    assert bb.nbytes() < pb.nbytes()
    # same adapter -> identical weights in both layouts (padding inert)
    i = pb.index("a-r8")
    b, loc = int(bb.adapter_bucket[i]), int(bb.adapter_local[i])
    np.testing.assert_array_equal(
        np.asarray(pb.data["q"]["A"][:, i, :, :8]),
        np.asarray(bb.data[b]["q"]["A"][:, loc, :, :8]))


def test_lora_idx_shapes(setup):
    cfg, _ = setup
    key = jax.random.PRNGKey(1)
    pb = build_bank(cfg, ADAPTERS, key, mode="padded")
    bb = build_bank(cfg, ADAPTERS, key, mode="bucketed")
    gi = jnp.asarray([0, 1, 2], jnp.int32)
    assert pb.lora_idx(gi).shape == (3,)
    li = bb.lora_idx(gi)
    assert li.shape == (3, 2)
    # a-r8 -> bucket 0 row 0; b-r64 -> bucket 1 row 0; c-r8 -> bucket 0 row 1
    np.testing.assert_array_equal(np.asarray(li),
                                  [[0, 0], [1, 0], [0, 1]])


# -- numerical parity -----------------------------------------------------
def test_model_logits_allclose_across_modes(setup):
    """The acceptance bar: bucketed produces logits allclose to padded on
    the real compute path, for every hosted adapter."""
    cfg, params = setup
    key = jax.random.PRNGKey(2)
    pb = build_bank(cfg, ADAPTERS, key, mode="padded")
    bb = build_bank(cfg, ADAPTERS, key, mode="bucketed")
    toks = jnp.arange(1, 7)[None, :]
    for aidx in range(len(ADAPTERS)):
        gi = jnp.asarray([aidx], jnp.int32)
        lp, cp = M.prefill(cfg, params, toks, bank=pb.data,
                           lora_idx=pb.lora_idx(gi), cache_len=16,
                           cache_dtype=jnp.float32)
        lb, cb = M.prefill(cfg, params, toks, bank=bb.data,
                           lora_idx=bb.lora_idx(gi), cache_len=16,
                           cache_dtype=jnp.float32)
        np.testing.assert_allclose(np.asarray(lp), np.asarray(lb),
                                   atol=1e-5)
        nxt = jnp.argmax(lp, axis=-1).astype(jnp.int32)
        dp, _ = M.decode_step(cfg, params, cp, nxt, bank=pb.data,
                              lora_idx=pb.lora_idx(gi))
        db, _ = M.decode_step(cfg, params, cb, nxt, bank=bb.data,
                              lora_idx=bb.lora_idx(gi))
        np.testing.assert_allclose(np.asarray(dp), np.asarray(db),
                                   atol=1e-5)


def test_engine_tokens_identical_across_modes(setup):
    """Mixed-rank co-batched workload: bank_mode='bucketed' emits exactly
    the tokens of bank_mode='padded' on the real engine."""
    cfg, params = setup

    def run(mode):
        eng = ServingEngine(cfg, params, ADAPTERS, max_batch=4,
                            max_len=32, bank_mode=mode)
        reqs = [Request(i, ["a-r8", "b-r64", "c-r8"][i % 3],
                        list(range(1, 7 + i)), 4,
                        arrival=time.monotonic()) for i in range(4)]
        for r in reqs:
            eng.submit(r)
        eng.run_until_drained()
        return eng, [r.output for r in reqs]

    eng_p, out_p = run("padded")
    eng_b, out_b = run("bucketed")
    assert out_p == out_b
    assert eng_b.bank_mode == "bucketed"
    assert isinstance(eng_b.bank, tuple)        # per-bucket pytrees


def test_engine_bucketed_rebalance_midflight(setup):
    """Bucketed banks survive the mid-flight load/evict path: rebuilds
    remap slots to new (bucket, local) indices and requests complete."""
    cfg, params = setup
    eng = ServingEngine(cfg, params, {"a-r8": 8, "b-r16": 16},
                        max_batch=2, max_len=24, bank_mode="bucketed")
    req = Request(0, "b-r16", list(range(1, 7)), 4)
    eng.submit(req)
    eng.step()
    assert eng.active == 1
    eng.load_adapters({"z-r64": 64})        # adds a new bucket mid-flight
    assert eng.lora_bank.bucket_ranks == (8, 16, 64)
    assert not eng.evict_adapter("b-r16")   # in flight -> refused
    eng.run_until_drained()
    assert len(req.output) >= 4
    assert eng.evict_adapter("b-r16")
    assert eng.lora_bank.bucket_ranks == (8, 64)


def test_apply_bank_sgmv_modes_agree(setup):
    """The Pallas dispatch helper: padded sgmv and token-compacting
    bucketed sgmv produce the same delta from the same LoRABank ids."""
    cfg, _ = setup
    key = jax.random.PRNGKey(3)
    pb = build_bank(cfg, ADAPTERS, key, mode="padded")
    bb = build_bank(cfg, ADAPTERS, key, mode="bucketed")
    T = 12
    x = jax.random.normal(jax.random.PRNGKey(4), (T, cfg.d_model))
    aid = jnp.asarray([0, 1, 2] * (T // 3), jnp.int32)
    y_p = apply_bank_sgmv(x, pb, "q", 0, aid, interpret=True)
    y_b = apply_bank_sgmv(x, bb, "q", 0, aid, interpret=True)
    np.testing.assert_allclose(np.asarray(y_p), np.asarray(y_b),
                               atol=1e-4)


# -- cost model -----------------------------------------------------------
@pytest.mark.parametrize("mix", [
    {8: 500, 128: 100},
    {8: 100, 16: 100, 64: 100},
    {16: 1, 128: 1},
])
def test_prefill_bucketed_cheaper_for_mixed_batches(mix):
    s = ServerModel()
    total, max_r = sum(mix.values()), max(mix)
    assert s.prefill_time_bucketed(mix) < s.prefill_time(total, max_r)


def test_prefill_bucketed_equals_padded_single_bucket():
    s = ServerModel()
    assert s.prefill_time_bucketed({64: 800}) == \
        pytest.approx(s.prefill_time(800, 64))


def test_decode_bucketed_cheaper_for_mixed_batches():
    s = ServerModel()
    mixed = {8: 12, 128: 4}
    assert s.decode_time_bucketed(mixed) < s.decode_time(16, 128)
    assert s.decode_time_bucketed({128: 16}) == \
        pytest.approx(s.decode_time(16, 128))


def test_decode_time_seq_len_param():
    """The KV read term scales with seq_len (and the default reproduces
    the original hard-coded calibration)."""
    s = ServerModel()
    assert s.decode_time(16, 8, seq_len=2048) > s.decode_time(16, 8)
    assert s.kv_read_bytes(512) == pytest.approx(2 * 2 * 32 * 1024 * 512)


# -- simulated cluster ----------------------------------------------------
def test_sim_bucketed_shrinks_rank_skew():
    """The padded-mode P95 TTFT skew from co-batching heterogeneous
    ranks shrinks when the simulated servers run bucketed banks."""
    adapters = make_adapters(24, seed=1)
    trace = synth_trace(adapters, rps=25, duration=40,
                        popularity="powerlaw", alpha=1.0, seed=2)
    res = {}
    for mode in ("padded", "bucketed"):
        backend = SimBackend(2, timeout=60, bank_mode=mode,
                             adapter_nbytes={a.adapter_id: a.nbytes
                                             for a in adapters})
        cluster = LoRAServeCluster(backend, adapters,
                                   policy="slora-random", seed=3,
                                   warmup=10, network=NetworkModel())
        res[mode] = cluster.run(copy.deepcopy(trace))
    assert res["bucketed"].p95_ttft() < res["padded"].p95_ttft()
    assert res["bucketed"].completed() >= res["padded"].completed()
