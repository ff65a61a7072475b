"""DeepSeek-V2 at a smoke size on the CPU (``data/smoke-deepseek-v2.json``:
one dense and four expert layers, 4 of 8 routed experts held, top-2,
one shared expert, YaRN): the program through the engine against the
plain reference of ``chipbench/arch/deepseek_v2.py``, the expert share
against the uncut layer, and the counts by hand.

The program runs its matmuls in float32 here, as the reference does,
so logits agree to float32 rounding: the tolerances below allow for
sums taken in another order (the absorbed decode reassociates
(q_nope W_UK^T) c and (p c) W_UV), not for a term left out; the
adapters move each projection by about half its output, so a missing
delta shows at many times the tolerance."""
import dataclasses
import json
import shutil
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import experts
from chipbench import flops as F
from chipbench import harness, weights
from chipbench.tests.helpers import DATA, SECONDS, SEED

ds = harness.architecture(harness.ROOT, "deepseek_v2")
SMOKE = json.loads((DATA / "smoke-deepseek-v2.json").read_text())
SOURCE = (harness.ROOT / "chipbench" / "arch" / "deepseek_v2.py").read_text()
CELL = "smoke-deepseek-v2.smoke-mix"
RANKS = {"a-r8": 8, "b-r16": 16, "c-r32": 32}
# float32 on both sides: rounding of sums taken in another order
RTOL = 2e-4


def _params(cfg=SMOKE):
    return weights.make_params(ds, cfg, SEED)


def _adapter(aid, cfg=SMOKE):
    return weights.make_adapter(ds, cfg, SEED, aid, RANKS[aid])


def _close(got, want, rtol=RTOL):
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=rtol * scale)


def _engine(params, max_len=48):
    from repro.serving import ServingEngine
    cfg = ds.model_config(SMOKE)
    with weights.served_adapters(ds, SMOKE, SEED):
        return ServingEngine(cfg, params, RANKS, max_batch=2,
                             max_len=max_len)


def test_engine_prefill_then_absorbed_decode_matches_reference():
    """Two rows of different adapters (all four targets, ranks 8 and
    32) prefilled by the engine's own program, then decoded token by
    token through its latent cache by its absorbed decode program,
    teacher-forced: every position's logits against the reference's
    full forward pass."""
    params = _params()
    eng = _engine(params)
    P, T = 9, 6
    rows = [("a-r8", 3), ("c-r32", 11)]
    seqs = [(np.arange(P + T) * mul + 5) % SMOKE["vocab_size"]
            for _, mul in rows]
    for slot, ((aid, _), seq) in enumerate(zip(rows, seqs)):
        idx = eng.lora_bank.lora_idx(
            jnp.asarray([eng.adapter_ids.index(aid)], jnp.int32))
        logits, c1 = eng._prefill_fn(P)(eng.params,
                                        jnp.asarray(seq[None, :P]),
                                        eng.bank, idx)
        ref = ds.logits(SMOKE, params, _adapter(aid), seq)
        _close(logits[0], ref[P - 1])
        eng.cache = eng._merge_many(eng.cache, c1,
                                    jnp.asarray([slot], jnp.int32),
                                    jnp.full((1,), P, jnp.int32))
    idx = eng.lora_bank.lora_idx(jnp.asarray(
        [eng.adapter_ids.index(aid) for aid, _ in rows], jnp.int32))
    refs = [ds.logits(SMOKE, params, _adapter(aid), seq)
            for (aid, _), seq in zip(rows, seqs)]
    for t in range(P, P + T):
        toks = jnp.asarray([seq[t] for seq in seqs], jnp.int32)
        logits, eng.cache = eng._decode(eng.params, eng.cache, toks,
                                        eng.bank, idx)
        for b in range(2):
            _close(logits[b], refs[b][t])
    counts = eng.expert_counters()
    assert counts["steps"] == T


@pytest.mark.parametrize("mode", ["padded", "bucketed"])
def test_absorbed_matches_expanded_decode_with_kv_b_lora(mode):
    """The kv_b delta enters absorbed decode as two low-rank terms per
    query, from each row's own factors in either bank layout."""
    from repro.lora.bank import build_bank
    from repro.models import model as M
    cfg = ds.model_config(SMOKE)
    params = _params()
    with weights.served_adapters(ds, SMOKE, SEED):
        lb = build_bank(cfg, RANKS, None, mode=mode, n_layers=cfg.n_layers)
    bank = lb.data
    idx = lb.lora_idx(jnp.asarray([0, 2], jnp.int32))
    toks = jnp.asarray([[3, 9, 27, 81, 243, 729, 7]] * 2) % 4096
    toks = toks.at[1].set(toks[1][::-1])
    _, cache = M.prefill(cfg, params, toks[:, :-1], bank=bank,
                         lora_idx=idx, cache_len=16)
    got = {}
    for absorbed in (False, True):
        got[absorbed], _ = M.decode_step(cfg, params, cache, toks[:, -1],
                                         bank=bank, lora_idx=idx,
                                         mla_absorbed=absorbed)
    _close(got[True], got[False])
    # the kv_b delta is in both: without it the logits move far
    def drop(tree):
        return {t: w for t, w in tree.items() if t != "kv_b"}

    bare = drop(bank) if mode == "padded" else tuple(map(drop, bank))
    without, _ = M.decode_step(cfg, params, cache, toks[:, -1], bank=bare,
                               lora_idx=idx, mla_absorbed=True)
    assert float(jnp.abs(without - got[True]).max()) > \
        100 * RTOL * float(jnp.abs(got[True]).max())


def _share(first, held):
    return dict(SMOKE, first_held_expert=first, n_routed_experts=held)


def test_expert_shares_add_up_to_the_uncut_layer():
    """Each chip of a deployment holds its share; the partial outputs
    of the two shares, the shared expert counted once, are what the
    uncut reference layer gives."""
    from repro.models.ffn import held_moe_ffn
    uncut = _share(0, SMOKE["router_experts"])
    full = ds.params(uncut, jax.random.PRNGKey(3), jnp.float32)
    f = jax.tree.map(lambda t: t[0], full["blocks"]["ffn"])
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 7, SMOKE["hidden_size"]))
    with jax.default_matmul_precision("highest"):
        want, _ = ds._experts(uncut, lambda a, b: a @ b, x[0], f)
        shared = ds._swiglu(lambda a, b: a @ b, x[0], f["ws1"], f["ws3"],
                            f["ws2"])
        parts = []
        for first in (0, 4):
            cfg = ds.model_config(_share(first, 4))
            mine = dict(f, **{w: f[w][first:first + 4]
                              for w in ("we1", "we3", "we2")})
            y, (pairs, touched) = held_moe_ffn(cfg, mine, x)
            parts.append(y[0])
            assert 0 <= int(touched) <= 4
    _close(parts[0] + parts[1] - shared, want, rtol=1e-5)
    # and a share alone is not the layer
    assert float(jnp.abs(parts[0] - want).max()) > 1e-3


@pytest.mark.parametrize("norm", [False, True])
def test_norm_topk(norm):
    """Renormalising the top-k weights scales each token's routed sum by
    1 / (its top-k probability mass); the release does not."""
    from repro.models.ffn import held_moe_ffn, swiglu
    cfg = ds.model_config(_share(0, SMOKE["router_experts"]))
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, norm_topk=norm))
    full = ds.params(dict(SMOKE, n_routed_experts=8), jax.random.PRNGKey(5),
                     jnp.float32)
    f = jax.tree.map(lambda t: t[0], full["blocks"]["ffn"])
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 5, SMOKE["hidden_size"]))
    with jax.default_matmul_precision("highest"):
        y, _ = held_moe_ffn(cfg, f, x)
        routed = y - swiglu(f, x, shared=True)
        probs = jax.nn.softmax(x @ f["router"], axis=-1)
        mass = jax.lax.top_k(probs, cfg.moe.top_k)[0].sum(-1)
        cfg_off = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, norm_topk=False))
        y_off, _ = held_moe_ffn(cfg_off, f, x)
        routed_off = y_off - swiglu(f, x, shared=True)
    want = routed_off / mass[..., None] if norm else routed_off
    _close(routed, want, rtol=1e-5)
    assert bool(jnp.all(mass < 1.0))


def test_yarn_at_the_published_values():
    from repro.models.attention import mla_scale
    from repro.models.common import yarn_correction_range, yarn_freqs
    cfg = json.loads((harness.ROOT / "chipbench" / "configs"
                      / "deepseek-v2-lite.json").read_text())
    mc = ds.model_config(cfg)
    assert yarn_correction_range(64, 10000.0, mc.rope_scaling) == (10, 23)
    plain = 1.0 / 10000.0 ** (np.arange(0, 64, 2) / 64)
    mask = 1.0 - np.clip((np.arange(32) - 10) / 13, 0.0, 1.0)
    want = plain / 40 * (1 - mask) + plain * mask
    np.testing.assert_allclose(yarn_freqs(64, 10000.0, mc.rope_scaling),
                               want, rtol=1e-6)
    # the first 10 pairs keep their frequency, the last 9 are cut by 40
    np.testing.assert_allclose(want[:10], plain[:10])
    np.testing.assert_allclose(want[23:], plain[23:] / 40)
    assert mla_scale(mc) == pytest.approx(
        192 ** -0.5 * (0.1 * 0.707 * np.log(40) + 1) ** 2, rel=1e-12)
    assert mla_scale(mc) == pytest.approx(0.11472, abs=5e-6)
    inv, cs, sm = ds.yarn(cfg)
    np.testing.assert_allclose(inv, want, rtol=1e-6)
    assert cs == 1.0 and sm == pytest.approx(mla_scale(mc), rel=1e-12)


def _root(tmp, source=SOURCE):
    """A checkout-shaped directory with the smoke cell of the deepseek_v2
    architecture (``source``), reporting what the backlog cells do."""
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    like = "deepseek-v2-lite.moe-backlog"
    for m in bench["end_to_end"] + bench["per_layer"]:
        if like in m.get("workloads", [like]):
            m["workloads"] = [CELL]
    bench["configs"] = [{"name": "smoke-deepseek-v2", "source": "test",
                         "file": "chipbench/configs/smoke-deepseek-v2.json",
                         "reduced": [], "why": "CPU test"}]
    bench["workloads"] = [{"name": CELL, "config": "smoke-deepseek-v2",
                           "traffic": "smoke-mix", "chips": 1,
                           "why": "CPU test"}]
    (tmp / "chipbench" / "configs").mkdir(parents=True)
    (tmp / "chipbench" / "traffic").mkdir(parents=True)
    (tmp / "chipbench" / "arch").mkdir(parents=True)
    (tmp / "chipbench" / "arch" / "deepseek_v2.py").write_text(source)
    shutil.copy(DATA / "smoke-deepseek-v2.json",
                tmp / "chipbench" / "configs" / "smoke-deepseek-v2.json")
    shutil.copy(DATA / "smoke-mix.json",
                tmp / "chipbench" / "traffic" / "smoke-mix.json")
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


def test_smoke_cell_runs_correct_and_counts_its_routing(tmp_path):
    """A run of the smoke cell, as ``chipbench/experts.py`` makes it on
    the chip: correct, and the engines' counters of the window against
    the uniform-routing expectation ``scope_cost`` prices (2 rows, top-2
    of 8 experts, 4 held: 0.875 pairs, 0.75 held experts touched)."""
    cell = harness.load_cell(CELL, _root(tmp_path))
    got = experts.counters_run(harness, cell, SEED, SECONDS, False)
    assert got["correct"] is True, got["checks"]
    assert "output_tok_s" in got["metrics"]
    assert got["decode_steps"] > 20
    assert got["scope_cost_pairs"] == 2 * 2 * 4 / 8
    assert got["scope_cost_touched"] == pytest.approx(
        4 * (1 - (6 / 8) ** 2))
    for touched, pairs in zip(got["touched_per_layer"],
                              got["pairs_per_layer"]):
        assert 0 < touched <= pairs <= 2 * 2


def test_program_and_reference_route_alike_in_float32(tmp_path):
    cell = harness.load_cell(CELL, _root(tmp_path))
    got = experts.route_flips(cell, SEED, 2, 24)
    assert got["selections"] == 2 * 24 * 4 * 2
    assert got["differing"] == 0


def test_reference_without_the_kv_b_delta_is_not_correct(tmp_path):
    delta = ('    kv = proj(c, w_kvb, "kv_b")')
    assert SOURCE.count(delta) == 1
    broken = SOURCE.replace(delta, '    kv = mm(c, w_kvb)')
    cell = harness.load_cell(CELL, _root(tmp_path, broken))
    res = harness.run_cell(cell, SEED, SECONDS, False)
    assert res["correct"] is False, res["checks"]


def test_engine_names_its_held_experts_and_counts_their_routing():
    from repro.core.request import ServeRequest
    from repro.obs import Tracer, WallClock
    from repro.serving import ServingEngine
    cfg = ds.model_config(SMOKE)
    tracer = Tracer(clock=WallClock())
    with weights.served_adapters(ds, SMOKE, SEED):
        eng = ServingEngine(cfg, _params(), RANKS, max_batch=2,
                            max_len=32, tracer=tracer)
    before = eng.expert_counters()
    assert before == {"pairs": [0] * 4, "touched": [0] * 4, "steps": 0}
    eng.submit(ServeRequest(req_id=0, adapter_id="b-r16", rank=16,
                            prompt_len=8, output_len=5,
                            prompt=list(range(1, 9)),
                            arrival=time.monotonic()))
    eng.run_until_drained()
    got = eng.expert_counters()
    steps = got["steps"]
    assert steps == 4                       # tokens after the first
    # two rows a step, top-2 of 8 experts, 4 of them held
    for pairs, touched in zip(got["pairs"], got["touched"]):
        assert 0 <= touched <= 4 * steps and touched <= pairs
        assert pairs <= 2 * 2 * steps
    assert sum(got["pairs"]) > 0
    spans = [s for s in tracer.spans
             if s.name in ("decode.dispatch", "prefill.dispatch")]
    assert spans and all(s.attrs["experts_held"] == 4 for s in spans)


def test_counts_by_hand():
    cfg = json.loads((harness.ROOT / "chipbench" / "configs"
                      / "deepseek-v2-lite.json").read_text())
    d, L = 2048, 9
    attn = 2048 * 3072 + 2048 * 576 + 512 * 4096 + 2048 * 2048
    assert ds.attn_params(cfg) == attn
    expert = 3 * 2048 * 1408
    dense, shared, router = 3 * 2048 * 10944, 3 * 2048 * 2816, 2048 * 64
    assert ds.block_matmul_params(cfg) == L * attn + dense + 8 * (
        router + shared)
    # the sizes the configuration file states
    full = L * attn + dense + 8 * (router + shared + 8 * expert) \
        + 2 * 102400 * d
    assert full * 4 / 1e9 == pytest.approx(5.21, abs=0.01)
    assert F.adapter_params(ds.target_dims, cfg, 128) * 16 * 4 / 1e9 == \
        pytest.approx(1.21, abs=0.01)
    assert ds.kv_bytes_per_token(cfg) == 9 * 576 * 4 == 20736
    # held experts 8 rows touch and the pairs they make
    touched = 8 * (1 - (58 / 64) ** 8)
    assert ds.held_touched(cfg, 8) == pytest.approx(touched)
    assert ds.held_pairs(cfg, 8) == 8 * 6 * 8 / 64
    # one decode row of rank 8 whose new token is the 100th in context
    flops, nbytes = ds.decode_cost(cfg, [("a", 8, 100)])
    lora = (2048 + 3072) + (2048 + 576) + (512 + 4096) + (2048 + 2048)
    attn_ctx = 2 * L * 100 * 16 * (2 * 512 + 64)
    assert flops == int(2 * ds.block_matmul_params(cfg)
                        + 2 * 8 * (6 * 8 / 64) * expert
                        + 2 * 8 * L * lora + attn_ctx + 2 * d * 102400)
    one = 8 * (1 - 58 / 64)
    weights_read = (L * attn + dense + 8 * (router + shared + one * expert)
                    + L * (2 * d + 512) + d + d * 102400)
    assert nbytes == int(weights_read * 4) + 4 * d + 4 * L * 8 * lora \
        + 100 * 20736
    scope = ds.scope_cost(cfg, [("a", 8, 100), ("b", 16, 50),
                                ("a", 8, 7)])
    assert scope["lora"] == (2 * (8 + 16 + 8) * L * lora,
                             (8 + 16) * L * lora * 4)
    three = 8 * (1 - (58 / 64) ** 3)
    assert scope["experts"] == (int(2 * 8 * (3 * 6 * 8 / 64) * expert),
                                int(8 * three * expert * 4))
    # a prefill of 3 tokens: causal attention over 1+2+3 keys
    pf = ds.prefill_flops(cfg, [("a", 8, 3)])
    assert pf == int(2 * 3 * (ds.block_matmul_params(cfg) + 8 * L * lora)
                     + 2 * 8 * (3 * 6 * 8 / 64) * expert
                     + 2 * L * 16 * (128 + 64 + 128) * 6
                     + 2 * d * 102400)
