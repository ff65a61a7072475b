"""Operation and byte counts against hand counts, the generator's
promises, and the trace reduction on a small trace."""
import json
from collections import Counter

import pytest

from chipbench import flops as F
from chipbench import generator, trace
from chipbench.harness import ROOT, architecture, percentile

CONFIGS = ROOT / "chipbench" / "configs"
dense = architecture(ROOT, "dense")


def _cfg(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


# name: (matmul weights per layer per token, LoRA A+B per unit rank per
# layer, KV bytes per token) worked out by hand from the published widths
HAND = {
    "internlm2-1.8b": (
        2048 * 2048 + 2 * 2048 * 1024 + 2048 * 2048 + 3 * 2048 * 8192,
        (2048 + 2048) + 2 * (2048 + 1024) + (2048 + 2048),
        2 * 24 * 8 * 128 * 4),
    "stablelm-1.6b": (
        2048 * 2048 + 2 * 2048 * 2048 + 2048 * 2048 + 3 * 2048 * 5632,
        4 * (2048 + 2048),
        2 * 24 * 32 * 64 * 4),
}


@pytest.mark.parametrize("name", sorted(HAND))
def test_counts_match_hand_counts(name):
    cfg = _cfg(name)
    mm, lora, kv = HAND[name]
    V = cfg["vocab_size"]
    assert dense.block_matmul_params(cfg) == mm
    assert F.adapter_params(dense.target_dims, cfg, 16) == 24 * 16 * lora
    assert dense.kv_bytes_per_token(cfg) == kv
    # one decode row of rank 8 whose new token is the 100th in context
    flops, nbytes = dense.decode_cost(cfg, [("a", 8, 100)])
    assert flops == (2 * 24 * (mm + 8 * lora) + 2 * 24 * 2 * 100 * 2048
                     + 2 * 2048 * V)
    bias = (4096 if name == "internlm2-1.8b" else 2048 * 3) \
        if cfg["qkv_bias"] else 0
    weights = 4 * (24 * (mm + 2 * 2048 + bias) + 2048 + 2048 * V)
    assert nbytes == (weights + 4 * 2048 + 4 * 24 * 8 * lora + 100 * kv)
    # a prefill of 3 tokens: causal attention over 1+2+3 keys, last
    # position's logits only
    pf = dense.prefill_flops(cfg, [("a", 8, 3)])
    assert pf == (2 * 24 * 3 * (mm + 8 * lora) + 2 * 24 * 2 * 2048 * 6
                  + 2 * 2048 * V)


def test_published_sizes():
    # the sizes the configuration files quote
    assert dense.weight_bytes(_cfg("internlm2-1.8b")) == pytest.approx(
        6.80e9, rel=0.01)
    assert F.adapter_params(dense.target_dims, _cfg("internlm2-1.8b"),
                            1) * 4 == 1376256
    assert F.adapter_params(dense.target_dims, _cfg("stablelm-1.6b"),
                            1) * 4 == 1572864


def test_roofline_bound():
    peak = {"flops": 100.0, "hbm_bw": 10.0}
    assert F.least_seconds(1000, 50, peak) == 10.0
    assert F.least_seconds(100, 500, peak) == 50.0


MIX = json.loads((ROOT / "chipbench" / "tests" / "data"
                  / "smoke-mix.json").read_text())


def _plans(seed, mix=MIX):
    return generator.schedule(mix, seed=seed, warmup_s=2.0, window_s=20.0,
                              vocab_size=4096, block_s=5.0)


def test_same_seed_same_schedule():
    a, b = _plans(2 ** 31 + 3), _plans(2 ** 31 + 3)
    assert [(p.t, p.adapter_id, p.prompt_len, p.output_len, p.prompt)
            for p in a] == [(p.t, p.adapter_id, p.prompt_len,
                             p.output_len, p.prompt) for p in b]


def test_every_seed_serves_the_same_work():
    a, b = _plans(1), _plans(2)
    assert [(p.t, p.adapter_id) for p in a] == \
        [(p.t, p.adapter_id) for p in b]
    assert [p.prompt for p in a] != [p.prompt for p in b]

    def demand(plans):
        return Counter((int(p.t // 5.0), p.adapter_id, p.prompt_len,
                        p.output_len) for p in plans)

    assert demand(a) == demand(b)
    spec = MIX["prompt"]
    for p in a:
        assert spec["min"] <= p.prompt_len <= spec["max"]
        assert p.prompt_len % spec["round_up"] == 0
        assert len(p.prompt) == p.prompt_len
        assert 1 <= min(p.prompt) and max(p.prompt) < 4096


def test_backlog_is_due_at_once():
    mix = dict(MIX, arrivals={"kind": "backlog", "count": 50})
    plans = _plans(5, mix)
    assert len(plans) == 50 and all(p.t == 0.0 for p in plans)


def test_percentile_interpolates():
    assert percentile([4, 1, 3, 2], 50) == 2.5
    assert percentile(range(101), 95) == 95
    assert percentile([], 95) is None


def test_reduction_of_a_small_trace():
    kept = {"mark_ns": 0, "devices": [{"name": "/device:TPU:0", "lines": {
        "XLA Ops": [["a", 0, 10], ["b", 5, 15], ["c", 30, 10],
                    ["d", 60, 10]],
        "XLA Modules": [["jit__decode(7)", 0, 20], ["jit__prefill(3)",
                                                    30, 10]]}}]}
    red = trace.reduce(kept, (0, 50), [("decode server:0", 18, 35)])
    assert red["busy_s"] == pytest.approx(30e-9)
    assert red["window_s"] == pytest.approx(50e-9)
    assert red["modules_s"] == pytest.approx({"jit__decode": 20e-9,
                                              "jit__prefill": 10e-9})
    assert red["idle_gaps"] == [["decode server:0", pytest.approx(10e-9)],
                                ["host", pytest.approx(10e-9)]]
    assert trace.reduce({"mark_ns": 0, "devices": []}, (0, 1)) is None


def _sweep_union(iv):
    """Total length covered by intervals, by a sweep over sorted edges."""
    edges = sorted([(a, 1) for a, _ in iv] + [(b, -1) for _, b in iv])
    total, depth, last = 0.0, 0, None
    for x, step in edges:
        if depth > 0:
            total += x - last
        depth += step
        last = x
    return total


def test_reduction_of_a_recorded_v5e_trace():
    # 200 ms of the decode-heavy window of internlm2-1.8b.skew-drift-pinned on
    # one v5e (two engines stepped in turn), cut from a traced run
    kept = json.loads((ROOT / "chipbench" / "tests" / "data"
                       / "trace-v5e-skew-drift.json").read_text())
    lo, hi = kept["span_ns"]
    red = trace.reduce(kept, (lo, hi))
    lines = kept["devices"][0]["lines"]
    ops = [(max(s, lo), min(s + d, hi)) for _, s, d in lines["XLA Ops"]
           if s + d > lo and s < hi]
    assert red["busy_s"] == pytest.approx(_sweep_union(ops) * 1e-9)
    assert red["window_s"] == pytest.approx(0.2)
    decode = sum(min(s + d, hi) - max(s, lo)
                 for n, s, d in lines["XLA Modules"]
                 if n.startswith("jit__decode(") and s + d > lo and s < hi)
    assert red["modules_s"]["jit__decode"] == pytest.approx(decode * 1e-9)
    # ops run inside programs: busy time never exceeds program time
    assert red["busy_s"] <= sum(red["modules_s"].values()) * (1 + 1e-9)
    assert red["device_ops"][0][0] == "jit__decode"
    gaps = [g for _, g in red["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
    assert all(label == "host" for label, _ in red["idle_gaps"])
