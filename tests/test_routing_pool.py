"""Routing table phi-weighting + adapter store fetch/GC invariants."""
import random

import pytest
from hypothesis_shim import given, settings, st

from repro.cluster import NetworkModel
from repro.core import AdapterInfo, AdapterStore, RoutingTable


def test_route_respects_phi():
    table = RoutingTable({"a": {0: 0.25, 1: 0.75}}, seed=7)
    counts = {0: 0, 1: 0}
    for _ in range(4000):
        counts[table.route("a")] += 1
    frac = counts[1] / 4000
    assert 0.70 < frac < 0.80


def test_route_counts_tracked():
    table = RoutingTable({"a": {0: 1.0}, "b": {1: 1.0}})
    for _ in range(5):
        table.route("a", tokens=10)
    table.route("b", tokens=3)
    assert table.request_counts == {"a": 5, "b": 1}
    counts = table.reset_counts()
    assert counts["a"] == 5 and table.request_counts == {}


def _mk_pool(n_servers=4, n_adapters=6):
    adapters = [AdapterInfo(f"a{i}", 8, nbytes=1000 * (i + 1))
                for i in range(n_adapters)]
    pool = AdapterStore(n_servers, adapters, NetworkModel())
    placement = {a.adapter_id: {i % n_servers: 1.0}
                 for i, a in enumerate(adapters)}
    pool.seed(placement)
    return pool, adapters, placement


def _fetch(pool, server_id, adapter_id):
    """Start a fetch and land it at once; returns its plan."""
    plan = pool.start_fetch(server_id, adapter_id)
    pool.finish(plan)
    return plan


def test_pool_hit_is_free_miss_pays_fetch():
    pool, adapters, placement = _mk_pool()
    home = next(iter(placement["a0"]))
    plan = _fetch(pool, home, "a0")
    assert plan.hit and plan.latency == 0.0 and plan.nbytes == 0
    # placement moves a0 to another server; first access there fetches
    other = (home + 1) % 4
    pool.apply_placement({**placement, "a0": {other: 1.0}})
    plan = _fetch(pool, other, "a0")
    assert plan.latency > 0.0 and plan.nbytes == adapters[0].nbytes
    # second access on the new server is now a hit
    assert _fetch(pool, other, "a0").hit
    # fetch to a server NOT in the desired placement is transient: the
    # delete-after-copy step GC's it while the desired copy survives
    third = (home + 2) % 4
    _fetch(pool, third, "a0")
    assert pool.check_invariant()
    assert other in pool.index["a0"]


def test_pool_gc_after_migration_keeps_one_copy():
    pool, adapters, placement = _mk_pool()
    home = next(iter(placement["a0"]))
    new_home = (home + 2) % 4
    pool.apply_placement({**placement, "a0": {new_home: 1.0}})
    _fetch(pool, new_home, "a0")
    assert pool.index["a0"] == {new_home}   # old copy GC'd
    assert pool.check_invariant()


def test_pool_never_loses_sole_copy():
    pool, adapters, placement = _mk_pool()
    home = next(iter(placement["a1"]))
    # desired moves a1 elsewhere, but no access happens on the new server;
    # a hit on the old server must not evict the only copy
    pool.apply_placement({**placement, "a1": {(home + 1) % 4: 1.0}})
    assert _fetch(pool, home, "a1").hit   # still a hit on the old home
    assert pool.check_invariant()
    assert len(pool.index["a1"]) >= 1


@settings(max_examples=30, deadline=None)
@given(st.integers(min_value=0, max_value=10_000))
def test_pool_invariant_under_random_ops(seed):
    rng = random.Random(seed)
    pool, adapters, placement = _mk_pool(n_servers=3, n_adapters=5)
    aids = [a.adapter_id for a in adapters]
    for _ in range(60):
        op = rng.random()
        if op < 0.7:
            _fetch(pool, rng.randrange(3), rng.choice(aids))
        else:
            new_pl = {aid: {rng.randrange(3): 1.0} for aid in aids}
            pool.apply_placement(new_pl)
        assert pool.check_invariant()
    # accounting sanity
    assert pool.total_bytes() >= max(a.nbytes for a in adapters)
    assert pool.max_adapters_per_server() <= len(adapters)
