"""Streaming serving gateway: SSE ordering + token parity vs the batch
path, runtime adapter lifecycle over HTTP, per-tenant admission
fairness, graceful drain with zero lost tokens (both substrates), the
incremental cluster API itself, and snapshot-safe report percentiles."""
import asyncio
import copy
import http.client
import json
import math
import os
import random
import signal
import subprocess
import sys
import threading
import time

import pytest

import jax

from repro.cluster import NetworkModel
from repro.configs import get_smoke_config
from repro.core import AdapterInfo, ServeRequest, UnknownAdapterError
from repro.models import model as M
from repro.serving import (ClusterReport, EngineBackend,
                           LoRAServeCluster, SimBackend)
from repro.server import AdmissionController, ServeGateway


# ---------------------------------------------------------------------
# harness: run the asyncio gateway in a thread, drive it over real HTTP
# ---------------------------------------------------------------------
class GatewayHarness:
    def __init__(self, cluster, **kw):
        self.gw = ServeGateway(cluster, port=0, **kw)
        self._ready = threading.Event()
        self.loop = None
        self.thread = threading.Thread(target=self._run, daemon=True)

    def _run(self):
        self.loop = asyncio.new_event_loop()
        asyncio.set_event_loop(self.loop)

        async def main():
            await self.gw.start()
            self._ready.set()
            await self.gw.serve_until_stopped()

        try:
            self.loop.run_until_complete(main())
        finally:
            self.loop.close()

    def __enter__(self):
        self.thread.start()
        assert self._ready.wait(60), "gateway failed to start"
        return self

    def shutdown(self, timeout=120):
        """The SIGTERM path: ``begin_shutdown`` is exactly what the
        installed signal handler invokes."""
        if self.thread.is_alive():
            self.loop.call_soon_threadsafe(self.gw.begin_shutdown)
            self.thread.join(timeout)
        assert not self.thread.is_alive(), "gateway failed to drain"

    def __exit__(self, *exc):
        self.shutdown()

    @property
    def port(self):
        return self.gw.port


def http_json(port, method, path, body=None, headers=None):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
    hdrs = {"Content-Type": "application/json"}
    hdrs.update(headers or {})
    conn.request(method, path,
                 json.dumps(body) if body is not None else None, hdrs)
    resp = conn.getresponse()
    raw = resp.read()
    out_headers = {k.lower(): v for k, v in resp.getheaders()}
    conn.close()
    try:
        parsed = json.loads(raw) if raw else {}
    except ValueError:
        parsed = raw.decode("utf-8", "replace")
    return resp.status, parsed, out_headers


def sse_request(port, payload, headers=None):
    """POST /v1/completions with stream=true; returns (status, chunks)
    where chunks are the decoded SSE frames up to ``[DONE]``."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=300)
    hdrs = {"Content-Type": "application/json"}
    hdrs.update(headers or {})
    conn.request("POST", "/v1/completions", json.dumps(payload), hdrs)
    resp = conn.getresponse()
    if resp.status != 200:
        resp.read()
        conn.close()
        return resp.status, []
    chunks = []
    while True:
        line = resp.fp.readline()
        if not line:
            break
        line = line.decode("utf-8").strip()
        if not line.startswith("data: "):
            continue
        data = line[len("data: "):]
        if data == "[DONE]":
            break
        chunks.append(json.loads(data))
    conn.close()
    return 200, chunks


def tokens_of(chunks):
    out = []
    for c in chunks:
        out.extend(c.get("tokens") or [])
    return out


def make_sim_cluster(n_servers=2, n_adapters=4, seed=0, **kw):
    adapters = [AdapterInfo(f"a{i}-r{[8, 16, 32, 64][i % 4]}",
                            [8, 16, 32, 64][i % 4], nbytes=8 << 20)
                for i in range(n_adapters)]
    backend = SimBackend(n_servers, adapter_nbytes={
        a.adapter_id: a.nbytes for a in adapters})
    return LoRAServeCluster(backend, adapters,
                            network=NetworkModel(),
                            rebalance_period=kw.pop("rebalance_period",
                                                    1e9),
                            seed=seed, **kw), adapters


# ---------------------------------------------------------------------
# incremental cluster API (no HTTP): run() === submit/poll/drain
# ---------------------------------------------------------------------
def test_incremental_api_matches_batch_run():
    """Driving the same trace through submit/poll/drain reproduces the
    batch ``run()`` exactly: same routing, completions, and TTFTs —
    ``run`` really is a client of the incremental API."""
    def trace():
        rng = random.Random(3)
        return [ServeRequest(req_id=i, adapter_id=f"a{rng.randrange(4)}-"
                             f"r{[8, 16, 32, 64][rng.randrange(4) % 4]}",
                             prompt_len=16, output_len=6,
                             arrival=i * 0.02)
                for i in range(12)]

    # adapter ids in the synthetic trace must exist: build from the set
    reqs = trace()
    ranks = {r.adapter_id: int(r.adapter_id.split("-r")[1])
             for r in reqs}
    adapters = [AdapterInfo(aid, rk, nbytes=8 << 20)
                for aid, rk in sorted(ranks.items())]

    def make():
        be = SimBackend(2, adapter_nbytes={a.adapter_id: a.nbytes
                                           for a in adapters})
        return LoRAServeCluster(be, adapters,
                                network=NetworkModel(), seed=5)

    batch = make()
    batch_rep = batch.run(copy.deepcopy(reqs))

    inc = make()
    inc.start()
    todo = sorted(copy.deepcopy(reqs), key=lambda r: r.arrival)
    i, now = 0, 0.0
    while i < len(todo) or inc.pending():
        while i < len(todo) and todo[i].arrival <= now + 1e-12:
            inc.submit(todo[i], now)
            i += 1
        inc.poll(now)
        nxt = inc._next_time(now, i < len(todo),
                             todo[i].arrival if i < len(todo) else None)
        if nxt is None:
            break
        now = max(now, nxt)
    inc.drain()
    inc_rep = inc.report()

    assert inc.routed == batch.routed
    assert inc_rep.completed() == batch_rep.completed() == len(reqs)
    assert sorted(r.ttft for r in inc_rep.results) == \
        sorted(r.ttft for r in batch_rep.results)


def test_cluster_register_unregister_lifecycle():
    cluster, _ = make_sim_cluster()
    cluster.start()
    sid = cluster.register_adapter(AdapterInfo("newbie", 16,
                                               nbytes=8 << 20))
    assert "newbie" in cluster.meta
    assert cluster.orch.placement["newbie"] == {sid: 1.0}
    cluster.submit(ServeRequest(req_id=1, adapter_id="newbie",
                                prompt_len=8, output_len=4,
                                arrival=0.0), 0.0)
    evs = cluster.drain()
    assert any(e.kind == "finish" and e.req.req_id == 1 for e in evs)

    cluster.unregister_adapter("newbie")
    with pytest.raises(UnknownAdapterError):
        cluster.submit(ServeRequest(req_id=2, adapter_id="newbie",
                                    prompt_len=8, output_len=4,
                                    arrival=0.0), 0.0)
    cluster.drain()
    assert "newbie" not in cluster.meta
    assert "newbie" not in cluster.orch.store.meta
    rep = cluster.report()
    assert rep.registered == 1 and rep.unregistered == 1
    # double-unregister and unknown both raise the routing error
    with pytest.raises(UnknownAdapterError):
        cluster.unregister_adapter("newbie")


def test_unregister_busy_adapter_is_loss_free():
    """Retiring an adapter with a request in flight: the request keeps
    its full token budget; the copies leave only after it finishes."""
    cluster, adapters = make_sim_cluster()
    cluster.track_tokens = True
    cluster.start()
    aid = adapters[0].adapter_id
    req = ServeRequest(req_id=7, adapter_id=aid, prompt_len=16,
                       output_len=24, arrival=0.0)
    cluster.submit(req, 0.0)
    evs = cluster.poll(0.0)
    cluster.unregister_adapter(aid)
    assert cluster._retiring == {aid}     # busy: retire is pending
    evs += cluster.drain()
    toks = sum(len(e.tokens) for e in evs if e.req.req_id == 7)
    assert toks == 24                     # zero lost tokens
    assert not cluster._retiring
    assert aid not in cluster.meta
    assert cluster.report().unregistered == 1


# ---------------------------------------------------------------------
# report safety (satellite: mid-flight percentiles + snapshot())
# ---------------------------------------------------------------------
def test_report_percentiles_safe_on_empty_window():
    rep = ClusterReport(results=[], summary={}, rebalances=0,
                        placements=[], per_server_counts=[], timed_out=0,
                        fetches=0, fetch_bytes=0,
                        max_adapters_per_server=0, total_adapter_bytes=0,
                        memory_profile=[])
    assert math.isnan(rep.p50_ttft()) and math.isnan(rep.p95_ttft())
    assert rep.mean_tbt() == 0.0 and rep.p95_tbt() == 0.0
    assert rep.completed() == 0
    assert not rep.meets_slo(1.0)         # no data is not "meeting SLO"
    assert rep.slo_attainment(1.0) == 1.0


def test_snapshot_mid_flight():
    """snapshot() works with requests still in progress — nothing
    raises, unfinished requests are visible, percentiles only cover
    finished ones."""
    cluster, adapters = make_sim_cluster()
    cluster.start()
    for i in range(4):
        cluster.submit(ServeRequest(
            req_id=i, adapter_id=adapters[i % len(adapters)].adapter_id,
            prompt_len=16, output_len=50, arrival=0.0), 0.0)
    cluster.poll(0.0)                     # nothing finished yet
    snap = cluster.snapshot()
    assert snap.in_progress == 4 and snap.completed() == 0
    assert math.isnan(snap.p95_ttft())    # no raise on partial window
    cluster.drain()
    final = cluster.snapshot()
    assert final.in_progress == 0 and final.completed() == 4
    assert final.p95_ttft() > 0


# ---------------------------------------------------------------------
# gateway over SimBackend
# ---------------------------------------------------------------------
def test_gateway_sse_ordering_and_health():
    cluster, adapters = make_sim_cluster()
    with GatewayHarness(cluster) as h:
        status, health, _ = http_json(h.port, "GET", "/healthz")
        assert status == 200 and health["status"] == "ok"

        status, chunks = sse_request(h.port, {
            "adapter_id": adapters[0].adapter_id,
            "prompt_len": 16, "max_tokens": 10})
        assert status == 200
        # strictly ordered, gapless chunk indices; exact token budget
        seen = 0
        for c in chunks:
            assert c["index"] == seen
            seen += len(c["tokens"])
        assert seen == 10
        assert chunks[-1]["finish_reason"] == "stop"
        assert chunks[-1]["usage"]["completion_tokens"] == 10

        status, m, _ = http_json(h.port, "GET", "/metrics")
        assert status == 200
        assert "repro_gateway_streamed_tokens_total 10" in m
        assert "repro_cluster_completed_total 1" in m
    assert h.gw.final_report.completed() == 1


def test_gateway_unknown_adapter_404():
    cluster, _ = make_sim_cluster()
    with GatewayHarness(cluster) as h:
        status, body, _ = http_json(h.port, "POST", "/v1/completions",
                                    {"adapter_id": "ghost",
                                     "prompt_len": 8})
        assert status == 404 and "ghost" in body["error"]
        status, _, _ = http_json(h.port, "GET", "/nope")
        assert status == 404
        status, body, _ = http_json(h.port, "POST", "/v1/completions",
                                    {"prompt_len": 8})
        assert status == 400              # no adapter_id at all


def test_gateway_runtime_adapter_lifecycle():
    """register -> route -> complete -> delete over HTTP, with the
    adapter table reflecting every step."""
    cluster, _ = make_sim_cluster()
    with GatewayHarness(cluster) as h:
        status, created, _ = http_json(h.port, "POST", "/v1/adapters",
                                       {"adapter_id": "live", "rank": 16,
                                        "nbytes": 4 << 20})
        assert status == 201 and created["server"] in (0, 1)
        # duplicate register conflicts
        status, _, _ = http_json(h.port, "POST", "/v1/adapters",
                                 {"adapter_id": "live", "rank": 16})
        assert status == 409

        status, table, _ = http_json(h.port, "GET", "/v1/adapters")
        entry = {e["adapter_id"]: e for e in table["adapters"]}["live"]
        assert entry["rank"] == 16 and not entry["draining"]
        assert str(created["server"]) in {str(s) for s in
                                          entry["servers"]}

        status, chunks = sse_request(h.port, {"adapter_id": "live",
                                              "prompt_len": 8,
                                              "max_tokens": 5})
        assert status == 200 and len(tokens_of(chunks)) == 5

        status, body, _ = http_json(h.port, "DELETE",
                                    "/v1/adapters/live")
        assert status == 202 and body["draining"]
        status, body, _ = http_json(h.port, "POST", "/v1/completions",
                                    {"adapter_id": "live",
                                     "prompt_len": 8})
        assert status == 404              # retiring: routing is closed
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            _, table, _ = http_json(h.port, "GET", "/v1/adapters")
            if all(e["adapter_id"] != "live"
                   for e in table["adapters"]):
                break
            time.sleep(0.02)
        else:
            pytest.fail("retired adapter never left the table")
        status, _, _ = http_json(h.port, "DELETE", "/v1/adapters/live")
        assert status == 404
    rep = h.gw.final_report
    assert rep.registered == 1 and rep.unregistered == 1


def test_gateway_admission_fairness_429():
    """A greedy tenant saturating its inflight cap gets 429 +
    Retry-After while another tenant keeps admitting."""
    cluster, adapters = make_sim_cluster()
    admission = AdmissionController(max_inflight=1)
    with GatewayHarness(cluster, admission=admission) as h:
        aid = adapters[0].adapter_id
        got_tokens = threading.Event()
        result = {}

        def greedy_stream():
            conn = http.client.HTTPConnection("127.0.0.1", h.port,
                                              timeout=300)
            conn.request("POST", "/v1/completions",
                         json.dumps({"adapter_id": aid,
                                     "prompt_len": 16,
                                     "max_tokens": 400}),
                         {"Content-Type": "application/json",
                          "x-tenant": "greedy"})
            resp = conn.getresponse()
            result["status"] = resp.status
            n = 0
            while True:
                line = resp.fp.readline()
                if not line:
                    break
                line = line.decode().strip()
                if not line.startswith("data: "):
                    continue
                if line == "data: [DONE]":
                    break
                obj = json.loads(line[6:])
                n += len(obj.get("tokens") or [])
                if n:
                    got_tokens.set()
            result["tokens"] = n
            conn.close()

        t = threading.Thread(target=greedy_stream, daemon=True)
        t.start()
        assert got_tokens.wait(60), "greedy stream never started"

        # greedy's second request: over its cap -> 429 + Retry-After
        status, body, headers = http_json(
            h.port, "POST", "/v1/completions",
            {"adapter_id": aid, "prompt_len": 8, "max_tokens": 2,
             "stream": False}, headers={"x-tenant": "greedy"})
        assert status == 429
        assert float(headers["retry-after"]) > 0
        assert "max-inflight" in body["error"]

        # a polite tenant admits just fine at the same instant
        status, body, _ = http_json(
            h.port, "POST", "/v1/completions",
            {"adapter_id": aid, "prompt_len": 8, "max_tokens": 2,
             "stream": False}, headers={"x-tenant": "polite"})
        assert status == 200 and len(body["tokens"]) == 2

        t.join(300)
        assert result["tokens"] == 400    # greedy still completes
        assert admission.rejected.get("greedy", 0) >= 1
        assert "polite" not in admission.rejected


def test_gateway_sigterm_drain_zero_lost_tokens_sim():
    """SIGTERM (begin_shutdown — the handler the signal invokes) while
    streams are mid-flight: every open stream still delivers its full
    token budget, new work is refused, and the gateway exits clean."""
    cluster, adapters = make_sim_cluster()
    h = GatewayHarness(cluster)
    with h:
        budgets = [60, 80, 100, 120]
        results = [None] * len(budgets)

        def stream(i):
            status, chunks = sse_request(h.port, {
                "adapter_id": adapters[i % len(adapters)].adapter_id,
                "prompt_len": 16, "max_tokens": budgets[i]})
            results[i] = (status, len(tokens_of(chunks)),
                          chunks[-1].get("finish_reason")
                          if chunks else None)

        threads = [threading.Thread(target=stream, args=(i,),
                                    daemon=True)
                   for i in range(len(budgets))]
        for t in threads:
            t.start()
        # wait until all four are actually in flight, then pull the plug
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline and cluster.pending() < 4:
            time.sleep(0.005)
        assert cluster.pending() == 4
        h.loop.call_soon_threadsafe(h.gw.begin_shutdown)

        # draining: new completions are refused...
        status, _, _ = http_json(h.port, "POST", "/v1/completions",
                                 {"adapter_id":
                                  adapters[0].adapter_id,
                                  "prompt_len": 8})
        assert status == 503
        for t in threads:
            t.join(300)
    # ...but every in-flight stream finished with zero lost tokens
    for (status, n, reason), budget in zip(results, budgets):
        assert status == 200 and n == budget and reason == "stop"
    rep = h.gw.final_report
    assert rep.completed() == len(budgets) and rep.timed_out == 0
    assert h.gw.state == "stopped"


# ---------------------------------------------------------------------
# gateway over the real JAX engine
# ---------------------------------------------------------------------
@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("llama-7b-paper")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _engine_cluster(cfg, params, adapters, n_servers=2, max_len=40):
    be = EngineBackend(cfg, params, n_servers, max_batch=2,
                       max_len=max_len, seed=0)
    return LoRAServeCluster(be, adapters, network=NetworkModel(),
                            rebalance_period=1e9, seed=0)


def test_engine_e2e_register_stream_parity_busy_delete(setup):
    """The acceptance path on the real engine: register a new adapter
    over HTTP, stream a completion via SSE token-identical to the batch
    ``run()`` path, then DELETE a busy adapter mid-stream and observe a
    loss-free drain."""
    cfg, params = setup
    rng = random.Random(11)
    prompt = [rng.randrange(1, cfg.vocab_size) for _ in range(6)]
    base = [AdapterInfo("base-r8", 8, nbytes=8 << 20),
            AdapterInfo("busy-r16", 16, nbytes=16 << 20)]
    hot = AdapterInfo("hot-r8", 8, nbytes=8 << 20)

    # batch reference: same seed, "hot-r8" present from t=0. Bank
    # weights are keyed per adapter id, so a runtime registration must
    # produce bit-identical weights — and therefore identical tokens.
    ref_req = ServeRequest(req_id=0, adapter_id="hot-r8", rank=8,
                           prompt_len=len(prompt), output_len=6,
                           prompt=list(prompt), arrival=0.0)
    _engine_cluster(cfg, params, base + [hot]).run([ref_req])
    ref_tokens = list(ref_req.output)
    assert len(ref_tokens) == 6

    cluster = _engine_cluster(cfg, params, base)
    with GatewayHarness(cluster) as h:
        status, created, _ = http_json(h.port, "POST", "/v1/adapters",
                                       {"adapter_id": "hot-r8",
                                        "rank": 8, "nbytes": 8 << 20})
        assert status == 201

        status, chunks = sse_request(h.port, {"adapter_id": "hot-r8",
                                              "prompt": prompt,
                                              "max_tokens": 6})
        assert status == 200
        seen = 0
        for c in chunks:                  # ordered, gapless on the
            assert c["index"] == seen     # real engine too
            seen += len(c["tokens"])
        assert tokens_of(chunks) == ref_tokens

        # DELETE an adapter while its stream is mid-flight
        first_token = threading.Event()
        result = {}

        def busy_stream():
            conn = http.client.HTTPConnection("127.0.0.1", h.port,
                                              timeout=600)
            conn.request("POST", "/v1/completions",
                         json.dumps({"adapter_id": "busy-r16",
                                     "prompt": prompt,
                                     "max_tokens": 24}),
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            result["status"] = resp.status
            toks = []
            while True:
                line = resp.fp.readline()
                if not line:
                    break
                line = line.decode().strip()
                if line == "data: [DONE]":
                    break
                if not line.startswith("data: "):
                    continue
                obj = json.loads(line[6:])
                toks.extend(obj.get("tokens") or [])
                if toks:
                    first_token.set()
            result["tokens"] = toks
            conn.close()

        t = threading.Thread(target=busy_stream, daemon=True)
        t.start()
        assert first_token.wait(300), "busy stream never started"
        status, body, _ = http_json(h.port, "DELETE",
                                    "/v1/adapters/busy-r16")
        assert status == 202 and body["draining"]
        t.join(600)
        # the in-flight request survived the retire with its full budget
        assert result["status"] == 200 and len(result["tokens"]) == 24
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            _, table, _ = http_json(h.port, "GET", "/v1/adapters")
            if all(e["adapter_id"] != "busy-r16"
                   for e in table["adapters"]):
                break
            time.sleep(0.05)
        else:
            pytest.fail("retired adapter never left the table")
    rep = h.gw.final_report
    assert rep.completed() == 2 and rep.timed_out == 0
    assert rep.registered == 1 and rep.unregistered == 1


def test_gateway_sigterm_drain_zero_lost_tokens_engine(setup):
    cfg, params = setup
    adapters = [AdapterInfo("ea-r8", 8, nbytes=8 << 20),
                AdapterInfo("eb-r16", 16, nbytes=16 << 20)]
    cluster = _engine_cluster(cfg, params, adapters)
    rng = random.Random(2)
    prompts = [[rng.randrange(1, cfg.vocab_size) for _ in range(6)]
               for _ in range(2)]
    budgets = [14, 18]
    results = [None, None]
    h = GatewayHarness(cluster)
    with h:
        def stream(i):
            status, chunks = sse_request(h.port, {
                "adapter_id": adapters[i].adapter_id,
                "prompt": prompts[i], "max_tokens": budgets[i]})
            results[i] = (status, len(tokens_of(chunks)))

        threads = [threading.Thread(target=stream, args=(i,),
                                    daemon=True) for i in range(2)]
        for t in threads:
            t.start()
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline and cluster.pending() < 2:
            time.sleep(0.01)
        assert cluster.pending() == 2
        h.loop.call_soon_threadsafe(h.gw.begin_shutdown)
        status, _, _ = http_json(h.port, "POST", "/v1/completions",
                                 {"adapter_id": "ea-r8",
                                  "prompt_len": 4})
        assert status == 503
        for t in threads:
            t.join(600)
    for (status, n), budget in zip(results, budgets):
        assert status == 200 and n == budget
    assert h.gw.final_report.completed() == 2
    assert h.gw.state == "stopped"


def test_launch_server_real_sigterm_subprocess():
    """The actual signal path: spawn ``python -m repro.launch.server``,
    deliver a real SIGTERM, expect a clean drain and exit code 0."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath("src") + os.pathsep + \
        env.get("PYTHONPATH", "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.launch.server", "--backend",
         "sim", "--port", "0", "--servers", "2", "--adapters", "4"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env)
    try:
        line = proc.stdout.readline().strip()
        assert line.startswith("listening on "), line
        host, port = line.split()[-1].rsplit(":", 1)
        status, chunks = sse_request(int(port), {
            "adapter_id": "ad0-r8", "prompt_len": 8, "max_tokens": 4})
        assert status == 200 and len(tokens_of(chunks)) == 4
        proc.send_signal(signal.SIGTERM)
        out, _ = proc.communicate(timeout=120)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.communicate()
    assert proc.returncode == 0, out
    assert "gateway drained OK" in out
    assert "served=1" in out


def test_gateway_poll_failure_is_raised_to_the_caller():
    """A cluster whose ``poll`` raises (a device OOM or compiler error
    in the engine step) stops the gateway, and ``run_gateway`` raises
    that error instead of returning a report as if it had drained."""
    from repro.launch.server import run_gateway

    cluster, _ = make_sim_cluster()

    def poll(now=None):
        raise RuntimeError("device step failed")

    cluster.poll = poll
    lines = []
    with pytest.raises(RuntimeError, match="device step failed"):
        run_gateway(cluster, "127.0.0.1", 0, announce=lines.append)
    assert lines and lines[0].startswith("listening on ")


POLL_FAILURE_SCRIPT = r"""
import sys

from repro.launch import server
from repro.serving import LoRAServeCluster


def poll(self, now=None):
    raise RuntimeError("device step failed")


LoRAServeCluster.poll = poll
server.main(sys.argv[1:])
"""


def test_launcher_exits_nonzero_when_the_gateway_fails():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath("src") + os.pathsep + \
        env.get("PYTHONPATH", "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.run(
        [sys.executable, "-c", POLL_FAILURE_SCRIPT, "--backend", "sim",
         "--port", "0", "--servers", "2", "--adapters", "4"],
        capture_output=True, text=True, env=env, timeout=120)
    assert proc.returncode != 0, proc.stdout
    assert "device step failed" in proc.stderr
    assert "gateway drained OK" not in proc.stdout
