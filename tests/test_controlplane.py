"""SLO-driven control plane: drift detection against trace ground
truth, loss-free drains, scale-up under surge, and no-op stability."""
import copy

import pytest

from repro.cluster import NetworkModel
from repro.controlplane import (ClusterController, ControllerConfig,
                                DriftDetector, SLOSpec, SLOTracker,
                                TelemetryHub)
from repro.core import AdapterInfo, ServeRequest
from repro.core.pool import AdapterStore
from repro.core.routing import RetiredServerError, RoutingTable
from repro.serving import LoRAServeCluster, SimBackend
from repro.traces import (make_adapters, production_trace_with_meta,
                          synth_trace)


def _controller(min_servers, max_servers, **cfg_kw):
    cfg = dict(tick_period=5.0, min_servers=min_servers,
               max_servers=max_servers, patience=2, drain_patience=3,
               cooldown=15.0)
    cfg.update(cfg_kw)
    return ClusterController(SLOSpec(ttft=8.0, target=0.95, window=30.0),
                             ControllerConfig(**cfg))


# -- drift detection vs ground truth -----------------------------------

def _tick_rates(reqs, window=30.0, tick=5.0, horizon=None):
    """Replay arrivals into a TelemetryHub exactly as the controller
    does, yielding (t, head-filtered windowed rates) per tick."""
    hub = TelemetryHub(window=window)
    reqs = sorted(reqs, key=lambda r: r.arrival)
    horizon = horizon or max(r.arrival for r in reqs)
    t, i = tick, 0
    while t <= horizon + tick:
        while i < len(reqs) and reqs[i].arrival <= t:
            r = reqs[i]
            hub.observe_arrival(r.adapter_id, 0,
                                r.prompt_len + r.output_len, r.arrival)
            i += 1
        rates = hub.adapter_rates(t)
        floor = 0.02 * sum(rates.values())
        yield t, {a: v for a, v in rates.items() if v >= floor}
        t += tick


def test_detector_flags_surge_not_stable():
    """The Fig 10 surge adapter must be detected (as a surge) and the
    stable head adapter must stay silent, against the generator's own
    pattern labels."""
    reqs, meta = production_trace_with_meta(50, rps=20, duration=300,
                                            seed=3)
    patterns = meta["patterns"]
    surge = next(a for a, p in patterns.items() if p == "surge")
    stable_heads = [a for a, p in patterns.items()
                    if p == "stable" and a.endswith("-a0")]
    det = DriftDetector()
    for t, rates in _tick_rates(reqs, horizon=300):
        det.observe(rates, t)
    kinds = {e.kind for e in det.events_for(surge)}
    assert "surge" in kinds, f"surge adapter {surge} not flagged: {kinds}"
    for aid in stable_heads:
        assert not det.events_for(aid), \
            f"stable adapter {aid} falsely flagged"


def test_detector_direction_on_trends():
    reqs, meta = production_trace_with_meta(50, rps=20, duration=300,
                                            seed=3)
    patterns = meta["patterns"]
    det = DriftDetector()
    for t, rates in _tick_rates(reqs, horizon=300):
        det.observe(rates, t)
    rising = next(a for a, p in patterns.items() if p == "rising")
    falling = next(a for a, p in patterns.items() if p == "falling")
    assert any(e.kind in ("rising", "surge")
               for e in det.events_for(rising))
    assert any(e.kind in ("falling", "diurnal")
               for e in det.events_for(falling))


def test_detector_synthetic_shapes():
    """Direct unit check on clean signals: step up -> surge, slow ramp
    -> rising, flat -> nothing."""
    det = DriftDetector()
    for i in range(40):
        lvl = 10.0 if i < 20 else 30.0
        det.update("step", lvl, float(i))
    events = det.events_for("step")
    assert events and events[0].kind == "surge"
    assert events[0].time >= 20.0   # no detection before the step

    det2 = DriftDetector()
    for i in range(60):
        det2.update("ramp", 10.0 + i, float(i))
        det2.update("flat", 10.0, float(i))
    assert any(e.kind in ("rising", "surge")
               for e in det2.events_for("ramp"))
    assert not det2.events_for("flat")


# -- telemetry primitives ----------------------------------------------

def test_sliding_window_rate_divides_by_elapsed_not_now():
    """Regression: early-window rates used ``now`` as the divisor,
    assuming the clock started at 0 — a feed starting late (engine wall
    clock, offset-arrival trace) had its rates silently deflated."""
    from repro.controlplane.telemetry import SlidingWindow
    w = SlidingWindow(horizon=30.0)
    assert w.rate(100.0) == 0.0            # never pushed
    w.push(100.0, 50.0)
    w.push(105.0, 50.0)
    # 100 tokens over the 10s actually covered, not over 110s of clock
    assert w.rate(110.0) == pytest.approx(10.0)
    # once the window is saturated the divisor is the horizon
    w.push(140.0, 60.0)
    assert w.rate(145.0) == pytest.approx(w.total(145.0) / 30.0)
    # degenerate zero-elapsed feed must not divide by zero
    w2 = SlidingWindow(horizon=30.0)
    w2.push(7.0, 5.0)
    assert w2.rate(7.0) == pytest.approx(5.0)


def test_histogram_prometheus_bucket_semantics():
    from repro.controlplane.telemetry import Histogram
    h = Histogram(bounds=(0.1, 1.0, 10.0))
    for v in (0.05, 0.1, 0.5, 5.0, 100.0):
        h.observe(v)
    # `le` is inclusive: 0.1 lands in the 0.1 bucket
    assert list(h.cumulative()) == [(0.1, 2), (1.0, 3), (10.0, 4),
                                    ("+Inf", 5)]
    assert h.count == 5 and h.sum == pytest.approx(105.65)
    d = h.to_dict()
    assert d["buckets"][-1] == ("+Inf", 5) and d["count"] == 5


def test_hub_feeds_latency_histograms():
    hub = TelemetryHub(window=5.0)
    for i in range(4):
        hub.observe_completion(
            ServeRequest(req_id=i, adapter_id="a", arrival=0.0,
                         output_len=5, prefill_done=0.3, finish=1.0),
            float(i))
    snap = hub.snapshot(100.0)
    # windowed percentiles aged out; cumulative histograms did not
    assert snap["ttft_p95"] is None
    assert snap["ttft_hist"]["count"] == 4
    assert snap["tbt_hist"]["count"] == 4


# -- SLO tracker -------------------------------------------------------

def test_slo_tracker_windowed_attainment():
    spec = SLOSpec(ttft=1.0, target=0.9, window=10.0)
    tr = SLOTracker(spec)
    for t in range(5):
        tr.observe(ServeRequest(req_id=t, adapter_id="a", arrival=0.0,
                                prefill_done=0.5), float(t))
    assert tr.attainment(4.0) == 1.0
    for t in range(5, 10):
        tr.observe(ServeRequest(req_id=t, adapter_id="a", arrival=0.0,
                                prefill_done=5.0), float(t))
    assert tr.attainment(9.0) == 0.5
    assert tr.violated(9.0)
    # old scores age out of the window
    assert tr.attainment(16.0) == 0.0
    tr.observe_timeout(17.0)
    assert tr.sample_count(17.0) == 4
    assert tr.lifetime_attainment() == pytest.approx(5 / 11)


# -- store + routing drain/retire semantics ----------------------------

def test_store_drain_and_retire():
    adapters = [AdapterInfo(f"a{i}", 8, nbytes=1000) for i in range(6)]
    store = AdapterStore(3, adapters, NetworkModel())
    placement = {f"a{i}": {i % 3: 1.0} for i in range(6)}
    store.seed(placement)
    # re-place without server 2, then drain it
    new = {f"a{i}": {i % 2: 1.0} for i in range(6)}
    store.apply_placement(new, now=0.0)
    plans = store.drain_server(2, now=0.0)
    assert plans, "drain of a populated server must start migrations"
    assert all(p.mode == "drain" for p in plans)
    with pytest.raises(RuntimeError):
        store.retire_server(2)          # still holds copies / transfers
    store.poll(max(p.eta for p in plans) + 1.0)
    assert store.server_adapter_count(2) == 0
    assert store.check_invariant()
    store.retire_server(2)
    with pytest.raises(RuntimeError):
        store.start_fetch(2, "a0", now=99.0)
    assert store.live_servers() == [0, 1]


def test_routing_block_server():
    table = RoutingTable({"a": {0: 0.5, 1: 0.5}, "b": {1: 1.0}}, seed=0)
    table.block_server(0)
    for _ in range(20):
        assert table.route("a") == 1
    with pytest.raises(RetiredServerError):
        table.update({"a": {0: 1.0}})
    with pytest.raises(RetiredServerError):
        table.block_server(1)           # "b" would lose its only route


# -- closed loop on the simulated backend ------------------------------

def _sim_cluster(n, adapters, controller=None):
    backend = SimBackend(n, timeout=120,
                         adapter_nbytes={a.adapter_id: a.nbytes
                                         for a in adapters})
    return LoRAServeCluster(backend, adapters, policy="loraserve", seed=3,
                            network=NetworkModel(), controller=controller)


def _surge_trace(adapters, seed=2):
    """Quiet first half, heavy second half: the load step a static
    fleet cannot absorb."""
    lo = synth_trace(adapters, rps=4, duration=60,
                     popularity="exponential", seed=seed)
    hi = synth_trace(adapters, rps=26, duration=60,
                     popularity="exponential", seed=seed + 1)
    for r in hi:
        r.arrival += 60.0
    out = lo + hi
    for i, r in enumerate(sorted(out, key=lambda r: r.arrival)):
        r.req_id = i
    return out


def test_scale_up_restores_slo_under_surge():
    adapters = make_adapters(24, seed=1)
    trace = _surge_trace(adapters)
    res_static = _sim_cluster(2, adapters).run(copy.deepcopy(trace))
    auto = _sim_cluster(
        2, adapters, _controller(2, 6, patience=2, cooldown=10.0))
    res_auto = auto.run(copy.deepcopy(trace))
    assert res_auto.scale_ups >= 1
    first_up = next(a.time for a in res_auto.controller_actions
                    if a.kind == "scale-up")
    assert first_up >= 60.0   # surge starts at t=60
    assert res_auto.gpu_seconds > 0
    assert res_auto.final_servers > 2
    att_auto = res_auto.slo_attainment(8.0)
    att_static = res_static.slo_attainment(8.0)
    assert att_auto > att_static
    assert res_auto.p95_ttft() < res_static.p95_ttft()


def test_drain_is_loss_free_with_live_traffic():
    """Drains interleaved with live arrivals: every request finishes,
    and nothing is ever routed to a retired server."""
    adapters = make_adapters(24, seed=1)
    trace = synth_trace(adapters, rps=2.0, duration=150,
                        popularity="exponential", seed=2)
    ctrl = _controller(1, 6, drain_patience=2, cooldown=10.0)
    reqs = copy.deepcopy(trace)
    res = _sim_cluster(4, adapters, ctrl).run(reqs)
    assert res.retires >= 1, "fleet never shrank; test is vacuous"
    assert res.timed_out == 0
    assert res.completed() == len(trace)
    assert all(r.finished for r in res.results)
    retire_time = {a.server: a.time for a in res.controller_actions
                   if a.kind == "retire"}
    for r in res.results:
        if r.server in retire_time:
            assert r.arrival <= retire_time[r.server], \
                (f"req {r.req_id} routed to server {r.server} after "
                 f"it retired at {retire_time[r.server]}")
    # a retired server stops billing: strictly cheaper than keeping
    # the whole initial fleet up for the entire run
    end = max(r.finish for r in reqs)
    assert res.gpu_seconds < 4 * end


def test_controller_noop_on_stable_trace():
    """Stable demand on a right-sized fleet: no scaling, no drains, no
    drift-triggered rebalances."""
    adapters = make_adapters(24, seed=1)
    trace = synth_trace(adapters, rps=10, duration=90,
                        popularity="exponential", seed=2)
    ctrl = _controller(3, 6)   # min == initial n: drains impossible
    res = _sim_cluster(3, adapters, ctrl).run(copy.deepcopy(trace))
    assert res.scale_ups == 0
    assert res.drains == 0
    assert res.retires == 0
    assert [a for a in res.controller_actions
            if a.kind != "rebalance"] == []
    assert res.slo_attainment(8.0) >= 0.95


def test_facade_drain_loss_free_simbackend():
    """Same loss-free guarantee through the serving facade path
    (LoRAServeCluster + SimBackend + real AdapterStore data plane)."""
    adapters = make_adapters(16, seed=1)
    trace = synth_trace(adapters, rps=1.5, duration=100,
                        popularity="exponential", seed=2)
    ctrl = _controller(1, 5, drain_patience=2, cooldown=10.0)
    cluster = LoRAServeCluster(
        SimBackend(4, timeout=120), adapters, policy="loraserve",
        network=NetworkModel(), rebalance_period=15.0, controller=ctrl)
    rep = cluster.run(copy.deepcopy(trace))
    assert rep.retires >= 1
    assert rep.timed_out == 0
    assert rep.completed() == len(trace)
    retire_time = {a.server: a.time for a in rep.controller_actions
                   if a.kind == "retire"}
    for r in rep.results:
        if r.server in retire_time:
            assert r.arrival <= retire_time[r.server]


def test_backend_add_and_retire_server():
    b = SimBackend(2)
    sid = b.add_server()
    assert sid == 2 and b.n_servers == 3
    b.load_adapters(2, {"a0": 8})
    assert b.hosted_adapters(2) == {"a0": 8}
    b.retire_server(2)
    assert b.hosted_adapters(2) == {}


# -- satellites --------------------------------------------------------

def test_percentile_interpolates():
    from repro.serving.metrics import percentile
    assert percentile([1.0, 2.0], 50) == pytest.approx(1.5)
    vs = [float(v) for v in range(1, 101)]
    assert percentile(vs, 95) == pytest.approx(95.05)
    assert percentile(vs, 100) == 100.0
    assert percentile(vs, 0) == 1.0
    assert percentile([7.0], 99) == 7.0


def test_replay_exhausted_flag():
    """A truncated replay must say so instead of masquerading as a
    complete run (satellite on serving/scheduler.py)."""
    from repro.serving.metrics import MetricsCollector
    from repro.serving.scheduler import replay

    class StubEngine:
        def __init__(self, consume):
            self.queue, self.active = [], 0
            self.metrics = MetricsCollector()
            self._clock = lambda: 0.0
            self._consume = consume

        def submit(self, r):
            self.queue.append(r)

        def step(self):
            if self._consume and self.queue:
                self.metrics.record(self.queue.pop(0))

    reqs = [ServeRequest(req_id=i, adapter_id="a", arrival=0.0,
                         prefill_done=0.1) for i in range(5)]
    done = replay(StubEngine(consume=True), list(reqs))
    assert done["exhausted"] is False
    with pytest.warns(RuntimeWarning, match="truncated"):
        stuck = replay(StubEngine(consume=False), list(reqs),
                       max_iters=10)
    assert stuck["exhausted"] is True


def test_production_trace_meta_ground_truth():
    reqs, meta = production_trace_with_meta(50, rps=10, duration=60,
                                            seed=4)
    pats = meta["patterns"]
    assert set(pats.values()) == {"rising", "falling", "diurnal",
                                  "stable", "surge"}
    assert all(r.adapter_id in pats for r in reqs)
    heads = [a for a, p in pats.items() if p != "stable"]
    assert len(heads) == 4          # 5 head slots, one labeled stable
    assert meta["load_profile"] == "flat"
    _, meta2 = production_trace_with_meta(50, rps=10, duration=60,
                                          seed=4, load_profile="diurnal")
    assert meta2["load_profile"] == "diurnal"
