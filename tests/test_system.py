"""End-to-end system tests: the serving facade over real engines (mini
cluster), and the full simulated paper pipeline."""
import copy
import random

import jax

from repro.cluster import NetworkModel
from repro.configs import get_smoke_config
from repro.core import AdapterInfo
from repro.models import model as M
from repro.serving import (EngineBackend, LoRAServeCluster, Request,
                           SimBackend)
from repro.traces import make_adapters, production_trace


def test_mini_cluster_end_to_end():
    """Real JAX engines behind the paper's orchestrator: route requests,
    fetch adapters through the store, rebalance mid-run, drain, verify
    invariants."""
    rng = random.Random(0)
    cfg = get_smoke_config("llama-7b-paper")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    adapters = [AdapterInfo(f"ad{i}-r{r}", r, nbytes=r * 1000)
                for i, r in enumerate([8, 8, 64, 128])]
    # the first poll compiles the engines, far longer than one period:
    # at least one rebalance fires while requests are in flight
    cluster = LoRAServeCluster(
        EngineBackend(cfg, params, 2, max_batch=2, max_len=32),
        adapters, policy="loraserve", network=NetworkModel(), seed=0,
        rebalance_period=0.05)
    trace = []
    for i in range(8):
        aid = rng.choice(adapters).adapter_id
        prompt = [rng.randrange(1, cfg.vocab_size) for _ in range(8)]
        trace.append(Request(i, aid, prompt, 4))
    store = cluster.orch.store
    assert store.check_invariant()
    rep = cluster.run(trace)
    assert rep.completed() == 8
    assert rep.rebalances >= 1
    assert store.check_invariant()


def test_production_trace_pipeline():
    """Paper §V-F setup in miniature: production-like trace, 4 servers,
    LORASERVE completes within SLO while contiguous static placement
    struggles."""
    adapters = make_adapters(50, seed=1)
    trace = production_trace(50, rps=18, duration=120, seed=2)
    def run(policy):
        backend = SimBackend(4, adapter_nbytes={a.adapter_id: a.nbytes
                                                for a in adapters})
        cluster = LoRAServeCluster(backend, adapters, policy=policy,
                                   seed=3, warmup=30,
                                   network=NetworkModel())
        return cluster.run(copy.deepcopy(trace))

    lora = run("loraserve")
    cont = run("slora-contiguous")
    assert lora.timed_out == 0
    assert lora.p95_ttft() <= cont.p95_ttft() * 1.5


def test_dryrun_importable_without_flag_leak():
    """Importing launch modules must not set the 512-device flag
    globally (only executing dryrun as __main__ may)."""
    import repro.launch.mesh  # noqa: F401
    import repro.launch.specs  # noqa: F401
    assert len(jax.devices()) == 1
