"""Simulated cluster end to end (``LoRAServeCluster`` over
``SimBackend``) + paper-claim directions."""
import copy

import pytest

from repro.cluster import NetworkModel
from repro.serving import LoRAServeCluster, SimBackend
from repro.traces import make_adapters, synth_trace


@pytest.fixture(scope="module")
def setup():
    adapters = make_adapters(60, seed=1)
    trace = synth_trace(adapters, rps=22, duration=150,
                        popularity="exponential", seed=2)
    return adapters, trace


def _run(adapters, trace, policy, n=4, seed=3, timeout=60.0, warmup=40):
    backend = SimBackend(n, timeout=timeout,
                         adapter_nbytes={a.adapter_id: a.nbytes
                                         for a in adapters})
    cluster = LoRAServeCluster(backend, adapters, policy=policy,
                               seed=seed, warmup=warmup,
                               network=NetworkModel())
    return cluster.run(copy.deepcopy(trace))


def test_all_policies_complete(setup):
    adapters, trace = setup
    for pol in ["loraserve", "slora-random", "slora-contiguous",
                "toppings"]:
        res = _run(adapters, trace, pol)
        assert res.completed() + res.timed_out == len(trace)
        assert res.p50_ttft() >= 0


def test_loraserve_beats_random_on_skewed_trace(setup):
    """Paper Fig 19 direction: LORASERVE's P95 TTFT beats S-LoRA Random
    under skewed popularity."""
    adapters, trace = setup
    lora = _run(adapters, trace, "loraserve")
    rand = _run(adapters, trace, "slora-random")
    assert lora.p95_ttft() < rand.p95_ttft()


def test_loraserve_memory_beats_toppings(setup):
    """Paper Fig 18-bottom: Toppings replicates every adapter everywhere;
    LORASERVE stores only what each server needs."""
    adapters, trace = setup
    lora = _run(adapters, trace, "loraserve")
    top = _run(adapters, trace, "toppings")
    assert top.max_adapters_per_server == len(adapters)
    assert lora.max_adapters_per_server < len(adapters)
    assert lora.total_adapter_bytes < top.total_adapter_bytes


def test_loraserve_tbt_competitive(setup):
    """Fig 20: TBT similar or better (paper: up to 15% better)."""
    adapters, trace = setup
    lora = _run(adapters, trace, "loraserve")
    top = _run(adapters, trace, "toppings")
    assert lora.mean_tbt() < top.mean_tbt() * 1.10


def test_pool_fetches_only_for_dynamic_policy(setup):
    adapters, trace = setup
    lora = _run(adapters, trace, "loraserve")
    rand = _run(adapters, trace, "slora-random")
    assert rand.fetches == 0            # static placement never migrates
    assert lora.rebalances > 0


def test_weak_scaling_direction():
    """Fig 21: doubling servers roughly doubles sustainable load."""
    adapters = make_adapters(40, seed=5)
    t4 = synth_trace(adapters, rps=20, duration=120,
                     popularity="uniform", seed=6)
    t8 = synth_trace(adapters, rps=40, duration=120,
                     popularity="uniform", seed=6)
    r4 = _run(adapters, t4, "loraserve", n=4, seed=7, timeout=120.0,
              warmup=30)
    r8 = _run(adapters, t8, "loraserve", n=8, seed=7, timeout=120.0,
              warmup=30)
    # same per-server load => comparable tail latency (within 4x)
    assert r8.p95_ttft() < max(4 * r4.p95_ttft(), 2.0)
