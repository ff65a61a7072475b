"""Tiered adapter data plane: async FetchPlan lifecycle, coalescing,
GC-vs-in-flight safety, source selection under link load, host-cache
tier, rebalance prefetch, remote-read serving — and migrate-vs-
remote-read token parity on the real JAX engine."""
import copy
import random

import jax
import jax.numpy as jnp
import pytest
from hypothesis_shim import given, settings, st

from repro.cluster import NetworkModel
from repro.configs import get_smoke_config
from repro.core import AdapterInfo, AdapterStore, ServeRequest
from repro.lora.bank import build_bank
from repro.models import model as M
from repro.serving import (EngineBackend, LoRAServeCluster, Request,
                           ServingEngine, SimBackend)
from repro.traces import make_adapters, synth_trace


def _store(n_servers=4, n_adapters=6, nbytes=200_000_000, **kw):
    adapters = [AdapterInfo(f"a{i}", 8, nbytes=nbytes)
                for i in range(n_adapters)]
    store = AdapterStore(n_servers, adapters, NetworkModel(), **kw)
    placement = {a.adapter_id: {i % n_servers: 1.0}
                 for i, a in enumerate(adapters)}
    store.seed(placement)
    return store, adapters, placement


# ---------------------------------------------------------------- async
def test_async_fetch_lifecycle():
    store, adapters, placement = _store()
    store.apply_placement({**placement, "a0": {1: 1.0}})
    plan = store.start_fetch(1, "a0", now=0.0)
    assert not plan.hit and plan.latency > 0.0
    assert plan.eta == pytest.approx(plan.latency)
    assert plan.src_server == 0 and plan.source == "ib_gdr"
    # transfer in flight: copy not installed, source link occupied
    assert "a0" not in store.local[1]
    assert store.next_event_time(0.0) == pytest.approx(plan.eta)
    assert store.network.link_load(0, plan.eta / 2) == 1
    assert store.poll(plan.eta / 2) == []
    done = store.poll(plan.eta)
    assert [p.adapter_id for p in done] == ["a0"]
    assert "a0" in store.local[1] and 1 in store.index["a0"]
    assert store.network.link_load(0, plan.eta) == 0
    assert store.next_event_time(plan.eta) is None


def test_duplicate_inflight_fetches_coalesce():
    store, _, _ = _store()
    p1 = store.start_fetch(1, "a0", now=0.0)
    p2 = store.start_fetch(1, "a0", now=0.1)
    assert p2.coalesced and p2.eta == pytest.approx(p1.eta)
    assert store.fetches == 1 and store.coalesced == 1
    assert len(store.poll(p1.eta)) == 1


def test_gc_skips_adapters_with_transfers_in_flight():
    """Regression (old `_gc`-on-hit bug): a hit must not delete a peer
    copy that an in-flight fetch on another server is reading from."""
    adapters = [AdapterInfo("a0", 8, nbytes=100_000_000),
                AdapterInfo("a1", 8, nbytes=100_000_000)]
    store = AdapterStore(4, adapters, NetworkModel())
    store.seed({"a0": {0: 0.5, 1: 0.5}, "a1": {3: 1.0}})
    # placement drops server 0's copy; migration is lazy
    store.apply_placement({"a0": {1: 1.0}, "a1": {3: 1.0}})
    # server 2 starts fetching a0 — source selection picks server 0
    plan = store.start_fetch(2, "a0", now=0.0)
    assert plan.src_server == 0
    # a *hit* on server 1 runs GC: with the old pool this deleted the
    # undesired server-0 copy mid-transfer; now GC must skip a0
    hit = store.start_fetch(1, "a0", now=0.1)
    assert hit.hit
    assert 0 in store.index["a0"], "in-flight source copy was GC'd"
    # once the transfer lands, delete-after-copy GC runs as usual
    store.poll(plan.eta)
    assert store.index["a0"] == {1}
    assert store.check_invariant()
    # the dropped copies were demoted to the host tier, not lost
    assert store.tier(0, "a0") == "host"


def test_prefetch_on_rebalance_warms_new_copies():
    store, _, placement = _store()
    new = dict(placement)
    new["a0"] = {2: 1.0}        # a0 moves 0 -> 2
    plans = store.apply_placement(new, now=5.0, prefetch=True)
    assert [p.adapter_id for p in plans] == ["a0"]
    assert plans[0].mode == "prefetch" and store.prefetches == 1
    store.poll(plans[0].eta)
    assert "a0" in store.local[2]
    # first routed access is now a hit — no lazy migrate-on-miss
    assert store.start_fetch(2, "a0", now=plans[0].eta).hit


def test_source_selection_prefers_unloaded_link():
    adapters = [AdapterInfo("a0", 8, nbytes=100_000_000)]
    store = AdapterStore(4, adapters, NetworkModel())
    store.seed({"a0": {0: 0.5, 1: 0.5}})
    # saturate server 0's egress with a fat unrelated transfer
    store.network.begin_transfer(2 << 30, "ib_gdr", now=0.0, src_server=0)
    plan = store.start_fetch(3, "a0", now=0.0)
    assert plan.src_server == 1, "should route around the loaded link"


def test_host_cache_tier_serves_refetches():
    store, _, placement = _store()
    # migrate a0 away; the old HBM copy demotes to server 0's host cache
    store.apply_placement({**placement, "a0": {1: 1.0}})
    store.finish(store.start_fetch(1, "a0"))
    assert store.index["a0"] == {1}
    assert store.tier(0, "a0") == "host"
    # flip back: the refetch reads the local host tier, not a peer
    store.apply_placement({**placement, "a0": {0: 1.0}})
    plan = store.start_fetch(0, "a0", now=10.0)
    assert plan.source == "local_host"
    assert plan.latency < store.network.transfer_latency(
        plan.nbytes, "ib_gdr")
    store.poll(plan.eta)
    assert store.tier(0, "a0") == "hbm"


def test_remote_read_plan_and_background_warm():
    store, _, placement = _store()
    store.apply_placement({**placement, "a0": {1: 1.0}})
    plan = store.start_remote_read(1, "a0", now=0.0)
    assert plan.mode == "remote-read" and not plan.hit
    assert plan.read_peer == 0 and plan.token_penalty > 0.0
    assert not plan.blocking and plan.eta > 0.0
    assert store.remote_reads == 1
    # remote reads cost less per iteration than a blocking migrate fetch
    assert plan.token_penalty < plan.latency
    store.poll(plan.eta)
    assert "a0" in store.local[1]
    assert store.start_remote_read(1, "a0", now=plan.eta).hit


@settings(max_examples=25, deadline=None)
@given(st.integers(min_value=0, max_value=10_000))
def test_invariant_under_interleaved_rebalance_and_async_fetches(seed):
    """Satellite: 'every adapter lives on >= 1 server' must hold under
    any interleaving of async fetches, remote reads, rebalances (with
    and without prefetch), and completion polls."""
    rng = random.Random(seed)
    store, adapters, _ = _store(n_servers=3, n_adapters=5)
    aids = [a.adapter_id for a in adapters]
    now = 0.0
    for _ in range(80):
        now += rng.random() * 0.05
        op = rng.random()
        if op < 0.4:
            store.start_fetch(rng.randrange(3), rng.choice(aids), now=now)
        elif op < 0.6:
            store.start_remote_read(rng.randrange(3), rng.choice(aids),
                                    now=now)
        elif op < 0.8:
            pl = {aid: {rng.randrange(3): 1.0} for aid in aids}
            store.apply_placement(pl, now=now,
                                  prefetch=rng.random() < 0.5)
        else:
            store.poll(now)
        assert store.check_invariant()
    store.poll(now + 1e9)
    assert store.check_invariant()
    assert store.total_bytes() >= max(a.nbytes for a in adapters)


# ------------------------------------------------------ simulated cluster
def test_simulator_remote_read_and_prefetch_end_to_end():
    adapters = make_adapters(12, seed=1)
    trace = synth_trace(adapters, rps=10, duration=60,
                        popularity="shifting", seed=2)

    def run(**kw):
        backend = SimBackend(3, timeout=60,
                             adapter_nbytes={a.adapter_id: a.nbytes
                                             for a in adapters})
        cluster = LoRAServeCluster(backend, adapters, policy="loraserve",
                                   seed=3, network=NetworkModel(), **kw)
        return cluster.run(copy.deepcopy(trace))

    migrate = run()
    remote = run(access_mode="remote-read")
    pre = run(prefetch=True)
    for res in (migrate, remote, pre):
        assert res.completed() == len(trace)
    assert remote.remote_reads > 0
    assert pre.prefetches > 0
    assert migrate.remote_reads == 0 and migrate.prefetches == 0
    # remote-read never blocks on a fetch; migrate pays them on misses
    assert all(r.fetch_latency == 0.0 for r in remote.results)
    assert any(r.fetch_latency > 0.0 for r in migrate.results)


# ------------------------------------------------------- real JAX engine
@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("llama-7b-paper")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _trees_equal(a, b):
    return all(bool(jnp.array_equal(x, y)) for x, y in
               zip(jax.tree.leaves(a), jax.tree.leaves(b)))


@pytest.mark.parametrize("mode", ["padded", "bucketed"])
def test_bank_get_set_adapter_roundtrip(setup, mode):
    cfg, _ = setup
    bank = build_bank(cfg, {"a": 8, "b": 16, "c": 8},
                      jax.random.PRNGKey(1), mode=mode, n_layers=2)
    w = bank.get_adapter("b")
    assert w["q"]["A"].shape[-1] == 16
    before_a = bank.get_adapter("a")
    perturbed = jax.tree.map(lambda x: x + 1.0, w)
    bank2 = bank.set_adapter("b", perturbed)
    assert _trees_equal(bank2.get_adapter("b"), perturbed)
    assert _trees_equal(bank2.get_adapter("a"), before_a)


def test_engine_remote_install_serves_peer_bytes(setup):
    """install_adapter must serve the *peer's* bytes, not re-materialize
    locally: a perturbation on the peer propagates through the install,
    and unperturbed weights yield token-identical outputs."""
    cfg, params = setup
    eng0 = ServingEngine(cfg, params, {"a-r8": 8}, max_batch=2,
                         max_len=16)
    eng1 = ServingEngine(cfg, params, {"b-r16": 16}, max_batch=2,
                         max_len=16)
    w = eng0.adapter_weights("a-r8")
    eng1.install_adapter("a-r8", 8, w)
    assert _trees_equal(eng1.adapter_weights("a-r8"), w)
    # peer bytes, not local regeneration
    wp = jax.tree.map(lambda x: x + 0.5, w)
    eng0.lora_bank = eng0.lora_bank.set_adapter("a-r8", wp)
    eng0.bank = eng0.lora_bank.data
    eng2 = ServingEngine(cfg, params, {"b-r16": 16}, max_batch=2,
                         max_len=16)
    eng2.install_adapter("a-r8", 8, eng0.adapter_weights("a-r8"))
    assert _trees_equal(eng2.adapter_weights("a-r8"), wp)
    # token parity: local copy vs remote-installed copy
    prompt = list(range(1, 7))
    local = ServingEngine(cfg, params, {"a-r8": 8, "b-r16": 16},
                          max_batch=2, max_len=16)
    r_local = Request(0, "a-r8", prompt, 4)
    r_remote = Request(0, "a-r8", prompt, 4)
    local.submit(r_local)
    eng1.submit(r_remote)
    local.run_until_drained()
    eng1.run_until_drained()
    assert r_local.output == r_remote.output


def _mini_trace(adapters, cfg, n, duration):
    rng = random.Random(7)
    out = []
    for i in range(n):
        a = adapters[i % len(adapters)]
        prompt = [rng.randrange(1, cfg.vocab_size) for _ in range(6)]
        out.append(ServeRequest(req_id=i, adapter_id=a.adapter_id,
                                rank=a.rank, prompt_len=6, output_len=3,
                                prompt=prompt,
                                arrival=i * duration / n))
    return out


def test_engine_backend_access_mode_token_parity(setup):
    """Acceptance: migrate and remote-read produce identical tokens on
    the real engine backend (remote reads serve bit-identical weights)."""
    cfg, params = setup
    adapters = [AdapterInfo(f"ad{i}-r{r}", r, nbytes=r * 1_000_000)
                for i, r in enumerate([8, 8, 16, 32, 64, 16])]
    trace = _mini_trace(adapters, cfg, 12, duration=1.2)

    def run(access_mode):
        reqs = copy.deepcopy(trace)
        backend = EngineBackend(cfg, params, 2, max_batch=2, max_len=16)
        cluster = LoRAServeCluster(
            backend, adapters, policy="loraserve",
            network=NetworkModel(), rebalance_period=0.4, seed=5,
            access_mode=access_mode, prefetch=False)
        report = cluster.run(reqs)
        return report, {r.req_id: list(r.output) for r in reqs}

    mig, mig_tokens = run("migrate")
    rem, rem_tokens = run("remote-read")
    assert mig.completed() == len(trace)
    assert rem.completed() == len(trace)
    assert rem.access_mode == "remote-read"
    assert all(toks for toks in mig_tokens.values())
    assert mig_tokens == rem_tokens
