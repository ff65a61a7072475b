"""Chaos plane: fault injection, failure detection, and loss-free
recovery across the serving stack.

The claims under test, per ISSUE acceptance:

- kill-a-server under live traffic is **loss-free on both substrates**:
  every request finishes or is re-dispatched, with zero lost and zero
  duplicated tokens (stream watermarks), and the real engine's
  re-dispatched outputs are token-identical to a fault-free run;
- routing to a confirmed-dead server stops within one detector window,
  and windowed SLO attainment returns to its pre-fault level;
- a stalled fetch blows its per-attempt deadline and retries from an
  alternate source/tier; the per-peer circuit breaker walks
  closed -> open -> half-open -> closed deterministically;
- the heartbeat detector never confirms a healthy server dead, however
  violently the virtual clock jumps.
"""
import copy
import http.client
import json
import os
import random
import threading
import time

import pytest

import jax

from repro.cluster import NetworkModel
from repro.configs import get_smoke_config
from repro.core import AdapterInfo, ServeRequest
from repro.core.pool import AdapterStore, CircuitBreaker, FetchRetryPolicy
from repro.faults import FailureDetector, FaultPlan
from repro.models import model as M
from repro.serving import EngineBackend, LoRAServeCluster, SimBackend
from repro.traces import make_adapters, synth_trace

from test_server import GatewayHarness, http_json, sse_request

SLO_TTFT = 0.25


# ---------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------
def _attainment(reqs, t0=0.0, t1=float("inf")):
    """Windowed TTFT attainment over sim requests, bucketed by arrival;
    unfinished requests count as misses."""
    w = [r for r in reqs if t0 <= r.arrival < t1]
    if not w:
        return 1.0
    return sum(1 for r in w if r.prefill_done >= 0
               and r.ttft <= SLO_TTFT and r.finish >= 0) / len(w)


def _sim_cluster(adapters, plan, rebalance_period=6.0, window=0.5):
    """Three ``SimBackend`` servers with timeouts off (a lost request is
    the chaos plane's fault, not the reaper's), prefetching recovery
    re-warms, the SSD tier, and per-token events on."""
    backend = SimBackend(3, timeout=1e9,
                         adapter_nbytes={a.adapter_id: a.nbytes
                                         for a in adapters})
    return LoRAServeCluster(
        backend, adapters, policy="loraserve", seed=7,
        network=NetworkModel(), rebalance_period=rebalance_period,
        prefetch=True, fault_plan=plan, detector_window=window,
        durable_ssd=True, track_tokens=True)


def _drive(cluster, trace, max_steps=200_000):
    """submit/poll the trace on the virtual clock (what ``run`` does,
    but keeping every ClusterEvent for watermark accounting)."""
    trace = sorted(trace, key=lambda r: r.arrival)
    cluster.start()
    events, submits = [], []
    now, i, n = 0.0, 0, len(trace)
    for _ in range(max_steps):
        while i < n and trace[i].arrival <= now + 1e-12:
            cluster.submit(trace[i], now)
            submits.append((now, trace[i].req_id,
                            cluster.routed[trace[i].req_id]))
            i += 1
        events.extend(cluster.poll(now))
        if i >= n and cluster.backend.pending() == 0 \
                and not cluster.orch.draining:
            break
        nxt = cluster._next_time(now, i < n,
                                 trace[i].arrival if i < n else None)
        if nxt is None:
            break
        now = max(now, nxt)
    else:
        pytest.fail("drive loop did not drain")
    events.extend(cluster.drain())
    return events, submits


def _token_counts(events):
    """Tokens surfaced per request across the whole event stream —
    exactly-once accounting means this equals output_len, never more
    (duplicates) and never less (losses)."""
    counts = {}
    for ev in events:
        if ev.kind in ("token", "finish") and ev.tokens:
            counts[ev.req.req_id] = counts.get(ev.req.req_id, 0) \
                + len(ev.tokens)
    return counts


# ---------------------------------------------------------------------
# kill-a-server: discrete-event substrate
# ---------------------------------------------------------------------
def test_sim_kill_a_server_loss_free():
    """Crash a server mid-trace (and restore it later): every request
    completes with exactly its output_len tokens accounted, stranded
    work re-dispatches, and post-restore SLO attainment returns to the
    pre-fault level."""
    t_kill, t_restore, window = 8.0, 16.0, 0.5
    adapters = make_adapters(8, seed=3)
    trace = synth_trace(adapters, rps=14.0, duration=24.0,
                        popularity="shifting", prompt_len=128,
                        output_len=64, seed=11)
    cluster = _sim_cluster(
        adapters, FaultPlan.kill_one(t_kill, 0, t_restore),
        window=window)
    reqs = copy.deepcopy(trace)
    events, _ = _drive(cluster, reqs)
    res = cluster.report()

    assert res.server_failures == 1 and res.recoveries == 1
    assert res.redispatched >= 1
    # loss-free: every request finished, token ledger exact
    assert all(r.finished for r in res.results)
    assert all(r.decoded == r.output_len for r in reqs)
    assert _token_counts(events) == {r.req_id: r.output_len
                                     for r in reqs}
    assert len(res.results) == len(trace)
    # detection within one window of the crash
    (rec,) = res.recovery_records
    assert rec.server == 0
    assert abs(rec.detected_at - (t_kill + window)) < 1e-6
    assert rec.redispatched == res.redispatched
    # the SLO dips during the fault and restores after
    pre = _attainment(reqs, 0.0, t_kill)
    post = _attainment(reqs, t_restore)
    assert post >= pre - 1e-9


def test_sim_kill_without_restore_survivors_carry():
    """No restore: the two survivors absorb the victim's load and the
    run still drains loss-free."""
    adapters = make_adapters(6, seed=3)
    trace = synth_trace(adapters, rps=10.0, duration=18.0,
                        prompt_len=128, output_len=48, seed=9)
    cluster = _sim_cluster(adapters, FaultPlan.kill_one(6.0, 1),
                           rebalance_period=1e9)
    reqs = copy.deepcopy(trace)
    events, _ = _drive(cluster, reqs)
    res = cluster.report()
    assert res.server_failures == 1 and res.recoveries == 1
    assert all(r.finished for r in res.results)
    assert all(r.decoded == r.output_len for r in reqs)
    assert _token_counts(events) == {r.req_id: r.output_len
                                     for r in reqs}
    # nothing arriving after confirmation landed on the dead server
    (rec,) = res.recovery_records
    assert all(r.server != 1 for r in res.results
               if r.arrival > rec.detected_at)


def test_sim_stalled_recovery_fetches_retry_loss_free():
    """Hang the re-warm transfers launched at crash recovery: each
    stall hits a distinct transfer, blows its deadline and retries,
    and requests that coalesced onto a stalled transfer (quoted
    eta=inf) wake when the retried copy lands instead of waiting
    forever."""
    adapters = make_adapters(6, seed=3)
    trace = synth_trace(adapters, rps=10.0, duration=18.0,
                        popularity="shifting", prompt_len=128,
                        output_len=64, seed=9)
    plan = FaultPlan.kill_one(6.0, 0, 12.0)
    for i in range(4):
        plan = FaultPlan(plan.events
                         + FaultPlan.stall(6.5 + 0.002 * (i + 1)).events)
    cluster = _sim_cluster(adapters, plan)
    reqs = copy.deepcopy(trace)
    events, _ = _drive(cluster, reqs)
    res = cluster.report()
    assert res.fetch_timeouts >= 2
    assert res.fetch_retries == res.fetch_timeouts
    assert all(r.finished for r in res.results)
    assert _token_counts(events) == {r.req_id: r.output_len
                                     for r in reqs}
    assert all(r.fetch_latency < float("inf") for r in res.results)


# ---------------------------------------------------------------------
# kill-a-server: facade substrate (stream watermarks + routing stop)
# ---------------------------------------------------------------------
def test_facade_kill_a_server_loss_free_watermarks():
    """Kill a SimBackend server under the incremental API with token
    streaming on: the event stream carries each request's tokens
    exactly once (continuations resume at the watermark, no replay,
    no gap), and no request submitted after confirmation routes to the
    dead server."""
    t_kill, window = 3.0, 0.5
    adapters = [AdapterInfo(f"a{i}-r{[8, 16, 32, 64][i % 4]}",
                            [8, 16, 32, 64][i % 4], nbytes=8 << 20)
                for i in range(4)]
    backend = SimBackend(2, adapter_nbytes={a.adapter_id: a.nbytes
                                            for a in adapters})
    cluster = LoRAServeCluster(
        backend, adapters, network=NetworkModel(),
        rebalance_period=1e9, seed=0, track_tokens=True,
        fault_plan=FaultPlan.kill_one(t_kill, 0),
        detector_window=window, durable_ssd=True)
    rng = random.Random(4)
    trace = [ServeRequest(req_id=i, adapter_id=adapters[i % 4].adapter_id,
                          rank=adapters[i % 4].rank, prompt_len=64,
                          output_len=8 + rng.randrange(8),
                          arrival=i * 0.125)
             for i in range(48)]

    events, submits = _drive(cluster, copy.deepcopy(trace))
    report = cluster.report()

    assert report.server_failures == 1 and report.recoveries == 1
    assert report.completed() == len(trace)
    assert report.redispatched >= 1

    # stream watermarks: exactly-once token accounting per request
    counts = _token_counts(events)
    want = {r.req_id: r.output_len for r in trace}
    assert counts == want

    # routing to the confirmed-dead server stops within one detector
    # window of the crash (margin: one extra window for the poll grid)
    late = [(t, rid, sid) for t, rid, sid in submits
            if t >= t_kill + 2 * window]
    assert late, "trace must outlive the detection window"
    assert all(sid != 0 for _, _, sid in late)

    # ...and the SLO recovers once the survivor owns the full load
    by_id = {r.req_id: r for r in trace}
    pairs = [(by_id[r.req_id].arrival, r) for r in report.results]
    pre = [r for a, r in pairs if a < t_kill]
    post = [r for a, r in pairs if a >= t_kill + 2 * window]
    att = lambda rs: (sum(1 for r in rs if r.finished and r.ttft is not None
                          and r.ttft <= SLO_TTFT) / len(rs)) if rs else 1.0
    assert att(post) >= att(pre) - 1e-9


# ---------------------------------------------------------------------
# kill-a-server: real engine (token parity with the fault-free run)
# ---------------------------------------------------------------------
@pytest.fixture(scope="module")
def engine_setup():
    cfg = get_smoke_config("llama-7b-paper")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _engine_cluster(cfg, params, adapters, plan=None):
    be = EngineBackend(cfg, params, 2, max_batch=2, max_len=48, seed=0)
    return LoRAServeCluster(be, adapters, network=NetworkModel(),
                            rebalance_period=1e9, seed=0,
                            fault_plan=plan, detector_window=0.3,
                            durable_ssd=True)


def test_engine_kill_a_server_token_parity(engine_setup):
    """Crash one of two real JAX engine servers mid-run: stranded
    requests re-dispatch as continuations (re-prefill of prompt +
    already-emitted tokens), and every request's final output is
    bit-identical to a fault-free run — the strongest form of the
    zero-lost/zero-duplicated claim."""
    cfg, params = engine_setup
    rng = random.Random(2)
    adapters = [AdapterInfo("fa-r8", 8, nbytes=8 << 20),
                AdapterInfo("fb-r16", 16, nbytes=16 << 20)]

    def trace():
        return [ServeRequest(
            req_id=i, adapter_id=adapters[i % 2].adapter_id,
            rank=adapters[i % 2].rank,
            prompt_len=6, output_len=10,
            prompt=[rng.randrange(1, cfg.vocab_size) for _ in range(6)],
            arrival=0.15 * i) for i in range(8)]

    base = trace()               # one rng draw, replayed twice
    ref = copy.deepcopy(base)
    _engine_cluster(cfg, params, adapters).run(ref)
    want = {r.req_id: list(r.output) for r in ref}
    assert all(len(t) == 10 for t in want.values())

    chaotic = copy.deepcopy(base)
    cluster = _engine_cluster(cfg, params, adapters,
                              plan=FaultPlan.kill_one(0.25, 0))
    report = cluster.run(chaotic)

    assert report.server_failures == 1 and report.recoveries == 1
    assert report.completed() == len(base)
    got = {r.req_id: list(r.output) for r in chaotic}
    assert got == want           # token-identical despite the crash


# ---------------------------------------------------------------------
# fetch stall -> timeout -> retry from an alternate source
# ---------------------------------------------------------------------
def _store(n, adapters, **kw):
    return AdapterStore(n, adapters, network=NetworkModel(),
                        retry=FetchRetryPolicy(), **kw)


def test_fetch_stall_retries_from_alternate_peer():
    """Stall an in-flight transfer: the per-attempt deadline fires,
    the attempt fails, and — with the original peer's link down — the
    relaunch re-sources from the other replica and lands the copy."""
    adapters = [AdapterInfo("a", 16, nbytes=64 << 20)]
    store = _store(3, adapters)
    store.seed({"a": {0: 0.5, 2: 0.5}})
    store.desired["a"].add(1)            # routing wants a copy on 1

    plan = store.start_fetch(1, "a", now=0.0)
    assert plan.src_server == 0          # cheapest idle peer, lowest id
    assert store.stall_transfer(1, "a")
    store.network.set_link_down(0)       # and the old source goes dark

    p = store._inflight[(1, "a")]
    assert p.eta == float("inf") and p.deadline < float("inf")
    store.poll(p.deadline + 0.01)        # deadline blows -> backoff
    assert store.fetch_timeouts == 1
    p = store._inflight[(1, "a")]
    assert p.retry_at > 0 and p.source == "retry-wait"

    store.poll(p.retry_at + 0.01)        # backoff elapses -> relaunch
    assert store.fetch_retries == 1
    p = store._inflight[(1, "a")]
    assert p.src_server == 2             # alternate replica, not 0
    store.poll(p.eta + 0.01)
    assert "a" in store.local[1]         # copy landed


def test_fetch_stall_falls_back_to_ssd_tier_and_opens_breaker():
    """Three consecutive stalled attempts against the same peer open
    its circuit breaker; the next relaunch skips the poisoned peer and
    recovers the copy from the durable SSD tier."""
    adapters = [AdapterInfo("a", 16, nbytes=64 << 20)]

    def transcript():
        store = _store(2, adapters, durable_ssd=True)
        store.seed({"a": {0: 1.0}})
        store.desired["a"].add(1)        # routing wants a copy on 1
        store.start_fetch(1, "a", now=0.0)
        log = []
        for _ in range(3):
            assert store.stall_transfer(1, "a")
            p = store._inflight[(1, "a")]
            store.poll(p.deadline + 0.001)
            p = store._inflight[(1, "a")]
            log.append(("timeout", round(p.retry_at, 9)))
            store.poll(p.retry_at + 0.001)
            p = store._inflight[(1, "a")]
            log.append(("relaunch", p.source, p.src_server,
                        round(p.eta, 9)))
        p = store._inflight[(1, "a")]
        store.poll(p.eta + 0.001)
        log.append(("landed", "a" in store.local[1],
                    store.fetch_timeouts, store.fetch_retries,
                    store.breakers[0].opens, store.breakers[0].state))
        return log

    log = transcript()
    # the breaker opened on the third failure, so the final relaunch
    # came from the SSD tier, not peer 0
    assert log[-2][1] == "ssd" and log[-2][2] == -1
    landed, timeouts, retries, opens, state = log[-1][1:]
    assert landed and timeouts == 3 and retries == 3 and opens == 1
    # deterministic: the seeded jitter reproduces the exact schedule
    assert transcript() == log


def test_circuit_breaker_open_half_open_closed_determinism():
    br = CircuitBreaker(threshold=3, cooldown=1.0)
    assert br.allows(0.0) and br.state == "closed"
    br.record_failure(0.0)
    br.record_failure(0.1)
    assert br.allows(0.2) and br.state == "closed"   # under threshold
    br.record_failure(0.2)                           # third: opens
    assert br.state == "open" and br.opens == 1
    assert not br.allows(0.2) and not br.allows(1.19)
    assert br.allows(1.2)                            # cooldown elapsed
    assert br.state == "half-open"                   # single probe
    br.record_failure(1.3)                           # probe failed
    assert br.state == "open" and br.opens == 2
    assert br.allows(2.3) and br.state == "half-open"
    br.record_success()                              # probe landed
    assert br.state == "closed" and br.failures == 0
    assert br.allows(2.4)


def test_retry_backoff_deterministic_and_bounded():
    pol = FetchRetryPolicy()
    a = [pol.backoff(i, random.Random(42)) for i in range(12)]
    b = [pol.backoff(i, random.Random(42)) for i in range(12)]
    assert a == b
    assert all(x <= pol.max_backoff * (1 + pol.jitter) + 1e-12 for x in a)
    assert all(a[i] >= pol.base_backoff for i in range(len(a)))


# ---------------------------------------------------------------------
# failure detector: no false positives, ever
# ---------------------------------------------------------------------
def test_detector_no_false_positives_on_jumpy_clock():
    """Beat-then-check per poll: however far the virtual clock jumps
    between polls, a server the host still beats is never confirmed."""
    det = FailureDetector(window=0.5)
    now = 0.0
    rng = random.Random(0)
    for _ in range(200):
        now += rng.random() * 50.0       # jumps way past the window
        for sid in range(3):
            det.beat(sid, now)
        assert det.check(now) == []
    assert det.confirmed_count == 0
    # ...and a genuinely silent server is confirmed exactly once
    det.beat(0, now + 1.0)
    det.beat(1, now + 1.0)
    assert det.check(now + 1.0) == [2]
    det.beat(0, now + 10.0)              # survivors keep beating
    det.beat(1, now + 10.0)
    assert det.check(now + 10.0) == []   # 2 reported exactly once
    assert det.confirmed_count == 1


def test_healthy_cluster_run_confirms_nothing():
    """A fault-free facade run with a tiny detector window and a jumpy
    virtual clock (arrival gaps far exceed the window) confirms no
    server dead and records no failures."""
    adapters = [AdapterInfo(f"a{i}", 8, nbytes=8 << 20) for i in range(3)]
    backend = SimBackend(2, adapter_nbytes={a.adapter_id: a.nbytes
                                            for a in adapters})
    cluster = LoRAServeCluster(backend, adapters, network=NetworkModel(),
                               rebalance_period=1e9, seed=0,
                               detector_window=0.05, durable_ssd=True)
    trace = [ServeRequest(req_id=i, adapter_id=adapters[i % 3].adapter_id,
                          rank=8, prompt_len=64, output_len=8,
                          arrival=5.0 * i)       # gaps >> window
             for i in range(12)]
    report = cluster.run(copy.deepcopy(trace))
    assert report.completed() == len(trace)
    assert report.server_failures == 0 and report.recoveries == 0
    assert cluster.detector.confirmed_count == 0


def test_detector_window_validation():
    with pytest.raises(ValueError):
        FailureDetector(window=0.0)


# ---------------------------------------------------------------------
# gateway: client disconnect cancels the request and frees the slot
# ---------------------------------------------------------------------
def test_gateway_client_disconnect_cancels_and_frees():
    """Drop the TCP connection mid-stream: the gateway's EOF watcher
    cancels the request (no orphaned slot, admission released) and the
    next stream on the same adapter runs to completion."""
    adapters = [AdapterInfo("a0-r8", 8, nbytes=8 << 20)]
    backend = SimBackend(1, adapter_nbytes={a.adapter_id: a.nbytes
                                            for a in adapters})
    cluster = LoRAServeCluster(backend, adapters, network=NetworkModel(),
                               rebalance_period=1e9, seed=0,
                               track_tokens=True)
    with GatewayHarness(cluster) as h:
        conn = http.client.HTTPConnection("127.0.0.1", h.port,
                                          timeout=30)
        conn.request("POST", "/v1/completions",
                     json.dumps({"adapter_id": "a0-r8",
                                 "prompt_len": 64, "max_tokens": 512}),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 200
        line = resp.fp.readline()        # stream is live...
        assert line
        resp.close()                     # ...client vanishes
        conn.close()

        def disconnects():
            _, text, _ = http_json(h.port, "GET", "/metrics")
            for ln in text.splitlines():
                if ln.startswith("repro_gateway_client_disconnects_total"):
                    return int(float(ln.split()[-1]))
            return 0

        deadline = time.time() + 20
        while time.time() < deadline and disconnects() < 1:
            time.sleep(0.05)
        assert disconnects() == 1

        # slot and admission are free again: a full stream completes
        status, chunks = sse_request(h.port, {"adapter_id": "a0-r8",
                                              "prompt_len": 16,
                                              "max_tokens": 8})
        assert status == 200
        assert sum(len(c.get("tokens") or []) for c in chunks) == 8
    assert cluster.cancelled >= 1


# ---------------------------------------------------------------------
# seeded fault storm (CI sweeps REPRO_CHAOS_SEED across a matrix)
# ---------------------------------------------------------------------
CHAOS_SEED = int(os.environ.get("REPRO_CHAOS_SEED", "0"))


def test_sim_random_fault_storm_drains_loss_free():
    """A seeded Poisson fault storm: whatever the plan throws —
    overlapping crashes, link flaps, stalled transfers — the run
    drains, nothing is lost, every token ledger closes, and every
    confirmed crash leaves a well-formed recovery record."""
    adapters = make_adapters(6, seed=3)
    trace = synth_trace(adapters, rps=10.0, duration=18.0,
                        popularity="shifting", prompt_len=64,
                        output_len=32, seed=100 + CHAOS_SEED)
    plan = FaultPlan.random_plan(CHAOS_SEED, horizon=16.0, n_servers=3,
                                 rate=0.4)
    cluster = _sim_cluster(adapters, plan)
    events, _ = _drive(cluster, trace)
    res = cluster.report()
    assert all(r.finished for r in res.results)
    assert all(r.decoded == r.output_len for r in trace)
    assert _token_counts(events) == {r.req_id: r.output_len
                                     for r in trace}
    # sub-window flaps heal before detection and run no recovery, so
    # recoveries may trail failures but each one must be recorded
    assert res.recoveries == len(res.recovery_records)
    assert res.recoveries <= res.server_failures
    for rec in res.recovery_records:
        assert rec.recovered_at >= rec.detected_at
