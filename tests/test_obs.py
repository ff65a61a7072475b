"""Flight-recorder tracing layer: Perfetto export golden schema,
span-tree well-formedness, exact (sim) / sub-1% (engine) per-request
phase decomposition, sim-vs-engine span-name parity through the facade,
cost-model drift accounting, flight-recorder dump triggers, and the
Prometheus rendering of histograms + drift metrics."""
import copy
import json
import math
import os

import pytest

import jax

from repro.cluster import ClusterSimulator, NetworkModel
from repro.configs import get_smoke_config
from repro.controlplane import (ClusterController, ControllerConfig,
                                SLOSpec, TelemetryHub)
from repro.core import AdapterInfo, ServeRequest
from repro.models import model as M
from repro.obs import (REQUEST_PHASES, CostModelDrift, EventClock,
                       FlightRecorder, Span, Tracer, WallClock,
                       predict_span_seconds, record_request_spans,
                       to_perfetto, write_jsonl, write_perfetto)
from repro.serving import EngineBackend, LoRAServeCluster, SimBackend
from repro.server.prom import render_metrics
from repro.traces import make_adapters, synth_trace


# ---------------------------------------------------------------------
# workload helpers
# ---------------------------------------------------------------------
def _sim_run(n_servers=2, n_adapters=8, rps=6.0, duration=8.0, seed=3,
             controller=None, recorder=None, **sim_kw):
    adapters = make_adapters(n_adapters, seed=seed)
    trace = synth_trace(adapters, rps=rps, duration=duration,
                        prompt_len=96, output_len=24, seed=seed)
    tracer = Tracer(clock=EventClock())
    sim = ClusterSimulator(n_servers, adapters, policy="loraserve",
                           seed=seed, timeout=120.0, warmup=0.0,
                           rebalance_period=4.0, controller=controller,
                           tracer=tracer, flight_recorder=recorder,
                           **sim_kw)
    res = sim.run(trace)
    return res, tracer


def _facade_adapters():
    return [AdapterInfo("ea-r8", 8, nbytes=8 << 20),
            AdapterInfo("eb-r16", 16, nbytes=16 << 20)]


def _facade_trace(adapters, cfg=None, n=6, prompt_len=6, output_len=4):
    import random
    rng = random.Random(7)
    trace = []
    for i in range(n):
        a = adapters[i % len(adapters)]
        prompt = None
        if cfg is not None:
            prompt = [rng.randrange(1, cfg.vocab_size)
                      for _ in range(prompt_len)]
        trace.append(ServeRequest(
            req_id=i, adapter_id=a.adapter_id, rank=a.rank,
            prompt_len=prompt_len, output_len=output_len,
            prompt=prompt, arrival=0.15 * i))
    return trace


def _run_facade(backend, adapters, trace, tracer, recorder=None,
                controller=None):
    cluster = LoRAServeCluster(
        backend, adapters, policy="loraserve", network=NetworkModel(),
        rebalance_period=1e9, seed=0, controller=controller,
        tracer=tracer, flight_recorder=recorder)
    report = cluster.run(trace)
    return report, cluster


@pytest.fixture(scope="module")
def engine_setup():
    cfg = get_smoke_config("llama-7b-paper")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


# ---------------------------------------------------------------------
# span-tree well-formedness + decomposition
# ---------------------------------------------------------------------
def _request_trees(tracer):
    """{req_id: (root_span, {phase: child_span})} for finished reqs."""
    trees = {}
    for rid, spans in tracer.by_request().items():
        roots = [s for s in spans if s.name == "request"]
        if not roots:
            continue
        assert len(roots) == 1
        kids = {s.name: s for s in spans
                if s.parent_id == roots[0].span_id}
        trees[rid] = (roots[0], kids)
    return trees


def test_sim_span_tree_and_exact_decomposition():
    res, tracer = _sim_run()
    assert res.completed() > 0 and tracer.n_spans > 0
    by_id = {s.span_id: s for s in tracer.spans}
    for s in tracer.spans:
        if s.parent_id is None:
            continue
        parent = by_id.get(s.parent_id)     # no orphans
        assert parent is not None
        assert parent.start - 1e-9 <= s.start   # child within parent
        assert s.end <= parent.end + 1e-9
    trees = _request_trees(tracer)
    assert len(trees) == res.completed()
    for root, kids in trees.values():
        assert set(kids) == set(REQUEST_PHASES)
        total = sum(kids[p].duration for p in REQUEST_PHASES)
        # sim: decomposition telescopes exactly (event-clock stamps)
        assert math.isclose(total, root.duration,
                            rel_tol=0, abs_tol=1e-9)
    # root duration is the measured arrival->finish interval
    fin = {r.req_id: r for r in res.requests if r.finish is not None
           and r.finish >= 0}
    for rid, (root, _kids) in trees.items():
        assert math.isclose(root.duration,
                            fin[rid].finish - fin[rid].arrival,
                            rel_tol=0, abs_tol=1e-9)


def test_engine_decomposition_within_one_percent(engine_setup):
    cfg, params = engine_setup
    adapters = _facade_adapters()
    be = EngineBackend(cfg, params, 2, max_batch=2, max_len=40, seed=0)
    tracer = Tracer(clock=WallClock())
    report, _ = _run_facade(be, adapters,
                            _facade_trace(adapters, cfg), tracer)
    assert report.completed() > 0
    trees = _request_trees(tracer)
    assert len(trees) == report.completed()
    for root, kids in trees.values():
        assert set(kids) == set(REQUEST_PHASES)
        total = sum(kids[p].duration for p in REQUEST_PHASES)
        assert root.duration > 0
        assert abs(total - root.duration) / root.duration < 0.01


def test_sim_vs_engine_span_name_parity(engine_setup):
    """Both substrates, driven through the same facade, must emit the
    same span vocabulary — the whole point of one tracing layer."""
    cfg, params = engine_setup
    adapters = _facade_adapters()

    t_sim = Tracer(clock=EventClock())
    _run_facade(SimBackend(2), copy.deepcopy(adapters),
                _facade_trace(adapters), t_sim)

    t_eng = Tracer(clock=WallClock())
    be = EngineBackend(cfg, params, 2, max_batch=2, max_len=40, seed=0)
    _run_facade(be, copy.deepcopy(adapters),
                _facade_trace(adapters, cfg), t_eng)

    def vocab(tracer):
        # an engine's own step spans name its dispatches and device
        # waits, which a simulated server does not have
        return {s.name for s in tracer.spans
                if not (s.cat == "step" and s.track.startswith("server:"))}

    names_sim = vocab(t_sim)
    names_eng = vocab(t_eng)
    assert names_sim == names_eng
    assert {"request", "submit", "poll", "poll.store", "poll.step",
            "poll.drain", *REQUEST_PHASES} <= names_sim


# ---------------------------------------------------------------------
# step spans: the work inside one serving step
# ---------------------------------------------------------------------
def _within(child, parent, eps=1e-9):
    return parent.start - eps <= child.start and child.end <= parent.end + eps


def _only_child(spans, name, parent):
    kids = [s for s in spans if s.name == name and s.track == parent.track
            and _within(s, parent)]
    assert len(kids) == 1, (name, parent, kids)
    return kids[0]


def test_engine_step_spans_nest(engine_setup):
    cfg, params = engine_setup
    adapters = _facade_adapters()
    be = EngineBackend(cfg, params, 2, max_batch=2, max_len=40, seed=0)
    tracer = Tracer(clock=WallClock())
    report, cluster = _run_facade(be, adapters,
                                  _facade_trace(adapters, cfg), tracer)
    assert report.completed() > 0
    spans = tracer.spans
    # one clock: the tracer's zero is the backend's
    assert tracer.origin_ns == be._t0_ns
    assert abs(tracer.now() - be.wall_now()) < 1e-3

    def iteration(name):
        out = [s for s in spans if s.name == name and s.cat == "iteration"]
        assert out
        return out

    for dec in iteration("decode"):
        disp = _only_child(spans, "decode.dispatch", dec)
        sync = _only_child(spans, "decode.sync", dec)
        assert dec.start == disp.start <= disp.end == sync.start
        assert sync.end == dec.end
        toks = [s for s in spans if s.name == "decode.tokens"
                and s.track == dec.track and s.start == dec.end]
        assert len(toks) == 1 and toks[0].end >= dec.end
    for pre in iteration("prefill"):
        disp = _only_child(spans, "prefill.dispatch", pre)
        sync = _only_child(spans, "prefill.sync", pre)
        merge = _only_child(spans, "prefill.merge", pre)
        assert pre.start == disp.start <= disp.end == sync.start
        assert sync.end == merge.start <= merge.end == pre.end
        assert isinstance(disp.attrs["new_program"], bool)
        admit = [s for s in spans if s.name == "admit"
                 and s.track == pre.track and _within(pre, s)]
        assert len(admit) == 1
    # every engine span lies inside one of the cluster's polls
    polls = [s for s in spans if s.name == "poll"]
    engine = [s for s in spans if s.track.startswith("server:")
              and s.name != "bank.rebuild"]
    assert polls and engine
    for s in engine:
        assert any(_within(s, p) for p in polls), s
    for name in ("poll.store", "poll.step", "poll.drain"):
        assert len([s for s in spans if s.name == name]) == len(polls)
    subs = [s for s in spans if s.name == "submit"]
    assert len(subs) == len(report.results)
    assert all({"server", "adapter_id"} <= set(s.attrs) for s in subs)
    # the drift meter reads iteration spans only: nothing new to price
    assert cluster.cost_drift.unmatched == 0

    # a forced rebalance: an adapter the placement does not put on a
    # server is loaded there, and the rebalance evicts it again
    placed = cluster.orch.placement
    sid, aid = next((s, a.adapter_id) for s in range(be.n_servers)
                    for a in adapters if s not in placed[a.adapter_id])
    be.load_adapters(sid, {aid: cluster.meta[aid].rank})
    cluster._rebalance(1.0, cluster.clock())
    reb = tracer.named("rebalance")
    assert len(reb) == 1 and reb[0].attrs["removed"] >= 0
    rebuilt = [s for s in tracer.named("bank.rebuild")
               if _within(s, reb[0])]
    assert len(rebuilt) == 1
    assert aid not in be.hosted_adapters(sid)
    assert {"n_adapters", "max_rank", "bytes"} <= set(rebuilt[0].attrs)
    assert rebuilt[0].attrs["bytes"] > 0


def test_start_rebases_spans_recorded_before_it(engine_setup):
    """Engine work before the cluster starts (a warm-up) keeps its
    monotonic time when ``start`` moves the shared zero, so it lands
    before the run's zero and not inside its window."""
    import time
    cfg, params = engine_setup
    adapters = _facade_adapters()
    be = EngineBackend(cfg, params, 2, max_batch=2, max_len=40, seed=0)
    tracer = Tracer(clock=WallClock())
    cluster = LoRAServeCluster(be, adapters, policy="loraserve",
                               network=NetworkModel(), rebalance_period=1e9,
                               seed=0, tracer=tracer)
    assert tracer.origin_ns == be._t0_ns
    eng = next(e for e in be.engines if e is not None)
    aid = eng.adapter_ids[0]
    eng.submit(ServeRequest(req_id=-1, adapter_id=aid,
                            rank=eng.adapter_ranks[aid], prompt_len=4,
                            output_len=2, prompt=[1, 2, 3, 4]))
    eng.step()
    warm = {id(s): tracer.origin_ns + s.start * 1e9 for s in tracer.spans}
    assert tracer.named("decode")
    time.sleep(0.01)
    cluster.start()
    assert tracer.origin_ns == be._t0_ns
    assert all(s.end < 0 for s in tracer.spans)
    for s in tracer.spans:
        assert abs(tracer.origin_ns + s.start * 1e9 - warm[id(s)]) < 1e3


def test_engine_clock_reads_without_tracer(engine_setup):
    """Without a tracer the engine reads its clock as it always has:
    once per step for admission, twice per prefill group and twice per
    decode dispatch (the stamps requests carry)."""
    from repro.serving import ServingEngine
    cfg, params = engine_setup
    reads = [0]

    def clock():
        reads[0] += 1
        return 0.0

    for block in (1, 2):
        eng = ServingEngine(cfg, params, {"a": 8}, max_batch=2, max_len=40,
                            decode_block=block, clock=clock)
        reads[0] = 0
        eng.submit(ServeRequest(req_id=0, adapter_id="a", rank=8,
                                prompt_len=4, output_len=8,
                                prompt=[1, 2, 3, 4]))
        eng.step()
        assert reads[0] == 1 + 2 + 2
        reads[0] = 0
        eng.step()
        assert reads[0] == 1 + 2


# ---------------------------------------------------------------------
# Perfetto / JSONL export
# ---------------------------------------------------------------------
def test_perfetto_golden_schema(tmp_path):
    res, tracer = _sim_run(duration=4.0)
    doc = to_perfetto(tracer)
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    assert doc["displayTimeUnit"] == "ms"
    events = doc["traceEvents"]
    slices = [e for e in events if e["ph"] == "X"]
    metas = [e for e in events if e["ph"] == "M"]
    assert len(slices) == tracer.n_spans and metas
    for e in slices:
        assert {"name", "cat", "ph", "ts", "dur", "pid", "tid",
                "args"} <= set(e)
        assert e["ts"] >= 0 and e["dur"] >= 0       # microseconds
        assert "span_id" in e["args"]
    for m in metas:
        assert m["name"] == "process_name"
        assert "name" in m["args"]
    # round-trips through json and lands on disk
    path = os.path.join(tmp_path, "run.perfetto.json")
    n = write_perfetto(tracer, path)
    with open(path) as f:
        again = json.load(f)
    assert n == tracer.n_spans
    assert len(again["traceEvents"]) == len(events)


def test_jsonl_export_round_trip(tmp_path):
    _res, tracer = _sim_run(duration=3.0)
    path = os.path.join(tmp_path, "spans.jsonl")
    n = write_jsonl(tracer, path)
    with open(path) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    assert len(rows) == n == tracer.n_spans
    names = {r["name"] for r in rows}
    assert {"request", *REQUEST_PHASES} <= names
    for r in rows:
        assert r["end"] >= r["start"]


# ---------------------------------------------------------------------
# cost-model drift
# ---------------------------------------------------------------------
def test_sim_drift_is_zero_validating_the_plumbing():
    """Sim iteration spans carry the exact time the simulator charged,
    so modeled==measured up to float noise — any real bias here means
    the pairing (not the model) is broken."""
    res, _tracer = _sim_run()
    drift = res.cost_drift
    assert set(drift) >= {"prefill", "decode"}
    for phase in ("prefill", "decode"):
        d = drift[phase]
        assert d["count"] > 0 and d["modeled_s"] > 0
        assert abs(d["bias"]) < 1e-9
        assert d["mean_abs_rel_err"] < 1e-9


def test_engine_drift_pairs_measured_with_model(engine_setup):
    cfg, params = engine_setup
    adapters = _facade_adapters()
    be = EngineBackend(cfg, params, 1, max_batch=2, max_len=40, seed=0)
    tracer = Tracer(clock=WallClock())
    report, _ = _run_facade(be, adapters,
                            _facade_trace(adapters, cfg), tracer)
    drift = report.cost_drift
    assert set(drift) >= {"prefill", "decode"}
    for phase in ("prefill", "decode"):
        d = drift[phase]
        assert d["count"] > 0
        assert d["modeled_s"] > 0 and d["measured_s"] > 0
        assert math.isfinite(d["bias"])


def test_predict_span_seconds_shapes():
    from repro.cluster.costmodel import ServerModel
    model = ServerModel()
    pre = Span("prefill", 0.0, 1.0, cat="iteration", track="server:0",
               attrs={"tokens": 256, "max_rank": 16, "batch": 2,
                      "bank_mode": "padded"})
    dec = Span("decode", 0.0, 1.0, cat="iteration", track="server:0",
               attrs={"batch": 2, "max_rank": 16, "steps": 4,
                      "iters": 4, "bank_mode": "padded"})
    p, d = predict_span_seconds(model, pre), predict_span_seconds(
        model, dec)
    assert p and math.isclose(p, model.prefill_time(256, 16))
    assert d and math.isclose(d, 4 * model.decode_time(2, 16, steps=4))
    # precomputed prediction (sim path) wins over shape-based
    pre.attrs["predicted"] = 0.123
    assert predict_span_seconds(model, pre) == 0.123
    # non-iteration shapes yield None
    assert predict_span_seconds(
        model, Span("submit", 0.0, 0.0, cat="step",
                    track="control")) is None


# ---------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------
def test_flight_recorder_dumps_on_forced_slo_violation(tmp_path):
    """An impossible TTFT target forces a violation; the recorder must
    produce an audit record carrying the controller's decision inputs
    and the recent-span ring."""
    ctrl = ClusterController(
        SLOSpec(ttft=1e-4, target=0.99, window=10.0),
        ControllerConfig(tick_period=0.5, min_samples=1, patience=1,
                         max_servers=3))
    rec = FlightRecorder(capacity=512, out_dir=str(tmp_path),
                         min_interval=0.0)
    res, _tracer = _sim_run(controller=ctrl, recorder=rec)
    assert res.completed() > 0
    assert rec.n_dumps >= 1 and res.flight_dumps == rec.n_dumps
    reasons = {d["reason"] for d in rec.dumps}
    assert reasons & {"slo-violation", "scale-up"}
    by_reason = {d["reason"]: d for d in rec.dumps}
    d = by_reason.get("slo-violation") or by_reason["scale-up"]
    audit = d["audit"]
    for key in ("now", "violated", "attainment", "window_samples",
                "windowed_p95_ttft", "demand_servers"):
        assert key in audit
    assert d["spans"], "ring was empty at dump time"
    # on-disk artifacts: span dump + audit json per event
    files = sorted(os.listdir(tmp_path))
    assert any(f.endswith(".perfetto.json") for f in files)
    assert any(f.endswith(".audit.json") for f in files)
    apath = next(f for f in files if f.endswith(".audit.json"))
    with open(os.path.join(tmp_path, apath)) as f:
        on_disk = json.load(f)
    assert on_disk["reason"] in reasons and "spans" not in on_disk


def test_flight_recorder_ring_rate_limit_and_cap():
    rec = FlightRecorder(capacity=4, min_interval=5.0, max_dumps=2)
    for i in range(10):
        rec.observe(Span(f"s{i}", float(i), i + 0.5, track="t"))
    d0 = rec.dump("first", now=100.0)
    assert d0 is not None
    assert len(d0["spans"]) == 4          # ring kept only the newest 4
    assert d0["spans"][-1]["name"] == "s9"
    assert rec.dump("too-soon", now=101.0) is None   # rate-limited
    assert rec.suppressed == 1
    assert rec.dump("second", now=200.0) is not None
    assert rec.dump("over-cap", now=300.0) is None   # max_dumps hit
    assert rec.n_dumps == 2


def test_record_request_spans_skips_unfinished():
    t = Tracer(clock=EventClock())
    r = ServeRequest(req_id=0, adapter_id="a", rank=8, prompt_len=4,
                     output_len=4, arrival=1.0)
    assert record_request_spans(t, r) is None and t.n_spans == 0


# ---------------------------------------------------------------------
# /metrics rendering: histograms + drift families
# ---------------------------------------------------------------------
def test_prom_renders_histograms_and_drift():
    adapters = _facade_adapters()
    tracer = Tracer(clock=EventClock())
    report, cluster = _run_facade(SimBackend(2), adapters,
                                  _facade_trace(adapters, n=10), tracer)
    assert report.completed() > 0
    hub = cluster.hub
    text = render_metrics(report, hub.snapshot(cluster.clock()),
                          {"state": "serving"})
    assert "# TYPE repro_ttft_seconds histogram" in text
    assert 'repro_ttft_seconds_bucket{le="+Inf"}' in text
    assert "repro_ttft_seconds_sum" in text
    assert "repro_ttft_seconds_count" in text
    assert 'repro_costmodel_seconds_total{kind="modeled",phase="prefill"}' \
        in text
    assert 'repro_costmodel_drift_ratio{phase="decode"}' in text
    assert 'repro_costmodel_mean_abs_rel_err{phase="prefill"}' in text
    # bucket counts are cumulative and end at the total count
    lines = [ln for ln in text.splitlines()
             if ln.startswith("repro_ttft_seconds_bucket")]
    counts = [int(ln.rsplit(" ", 1)[1]) for ln in lines]
    assert counts == sorted(counts)
    total = int(next(ln for ln in text.splitlines() if ln.startswith(
        "repro_ttft_seconds_count")).rsplit(" ", 1)[1])
    assert counts[-1] == total == hub.ttft_hist.count


def test_prom_omits_empty_histograms_and_drift():
    from repro.serving import ClusterReport
    empty = ClusterReport(results=[], summary={}, rebalances=0,
                          placements=[], per_server_counts=[],
                          timed_out=0, fetches=0, fetch_bytes=0,
                          max_adapters_per_server=0,
                          total_adapter_bytes=0, memory_profile=[])
    hub = TelemetryHub()
    text = render_metrics(empty, hub.snapshot(0.0),
                          {"state": "serving"})
    assert "repro_ttft_seconds_bucket" not in text
    assert "repro_costmodel" not in text
