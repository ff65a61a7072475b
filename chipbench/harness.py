"""One run of one cell: set-up, the measured window, the drain, the
check against the plain reference, and the result line.

The window drives the program's incremental cluster API,
``LoRAServeCluster.submit`` / ``poll`` over ``EngineBackend``, from an
open-loop schedule (or a backlog queued at time 0). Each request's
``arrival`` is its scheduled time on ``cluster.clock()``, so its time to
first token includes any time the poll loop held it back.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import importlib.util
import json
import math
import random
import resource
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

from . import flops as F
from . import generator, weights
from .peaks import peaks

ROOT = Path(__file__).resolve().parents[1]
TRACE_SECONDS = 4.0
# a poll longer than this is logged with what the process did meanwhile
STALL_S = 0.5
# the plain reference's matmul precision in the check
REFERENCE_MATMUL = "highest"


def log(*a) -> None:
    print("[chipbench]", *a, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# cells
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    arch: object                        # the configuration's architecture
    mix: dict
    chips: int
    end_to_end: List[dict]
    per_layer: List[dict]


def _for_cell(metrics: List[dict], cell: str) -> List[dict]:
    return [m for m in metrics if cell in m.get("workloads", [cell])]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json``, with its
    configuration and traffic files and the metrics it reports."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((root / cfg["file"]).read_text())
    if "architecture" not in config:
        raise KeyError(f"{cfg['file']} names no 'architecture': the "
                       f"module chipbench/arch/<architecture>.py that "
                       f"builds, counts and checks its model")
    arch = architecture(root, config["architecture"])
    mix = generator.load_mix(generator.mix_path(root, w["traffic"]))
    return Cell(name, config, arch, mix, int(w["chips"]),
                _for_cell(bench["end_to_end"], name),
                _for_cell(bench["per_layer"], name))


@functools.lru_cache(maxsize=None)
def _module(path: Path, name: str):
    """The Python file ``path``, loaded once per process."""
    if not path.is_file():
        raise FileNotFoundError(f"no {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def architecture(root: Path, name: str):
    """The module ``chipbench/arch/<name>.py`` of the checkout ``root``
    (``chipbench/arch/dense.py`` lists what it defines)."""
    path = (root / "chipbench" / "arch" / f"{name}.py").resolve()
    return _module(path, f"chipbench_arch_{name.replace('.', '_')}")


def reader(metric: str):
    """``read(run)`` of ``chipbench/metrics/<metric>.py``."""
    path = ROOT / "chipbench" / "metrics" / f"{metric}.py"
    return _module(path, f"chipbench_metric_{metric.replace('.', '_')}").read


# ---------------------------------------------------------------------------
# what the readers see
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Req:
    """One request of the measured set, on the cluster clock."""
    idx: int
    adapter_id: str
    rank: int
    t_sched: float
    t_submit: float
    prompt_len: int
    output_len: int
    prefill_start: Optional[float] = None
    t_first: Optional[float] = None
    t_finish: Optional[float] = None
    n_out: int = 0
    finished: bool = False

    def ttft(self, give_up: float) -> float:
        """Time to first token; a request that never got one counts as
        having waited until the run gave up on it."""
        end = self.t_first if self.t_first is not None else give_up
        return end - self.t_sched

    def tpot(self) -> Optional[float]:
        if not self.finished or self.n_out < 2:
            return None
        return (self.t_finish - self.t_first) / (self.n_out - 1)


@dataclasses.dataclass
class Run:
    cell: str
    mix: dict
    setup_s: float
    window: tuple                       # (start, end) on the cluster clock
    give_up: float                      # cluster time the drain stopped
    requests: List[Req]                 # the measured set
    tokens_in_window: int = 0
    counters: Dict[str, float] = dataclasses.field(default_factory=dict)
    decode_spans: List[float] = dataclasses.field(default_factory=list)
    profile: Optional[dict] = None      # traced runs only


def percentile(values, p: float) -> Optional[float]:
    """Linear interpolation between closest ranks (numpy's default)."""
    vs = sorted(values)
    if not vs:
        return None
    pos = p / 100.0 * (len(vs) - 1)
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(vs) - 1)
    return vs[lo] + (vs[hi] - vs[lo]) * (pos - lo)


# ---------------------------------------------------------------------------
# compile counting (a copy of chip_smoke.py's CompileCounter)
# ---------------------------------------------------------------------------


class CompileCounter:
    """XLA compilations and persistent-cache hits, through
    ``jax.monitoring``."""

    def __init__(self):
        import jax.monitoring
        self.compiles = 0
        self.hits = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_dur(self, event, duration_secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += duration_secs

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


# ---------------------------------------------------------------------------
# building the system under test
# ---------------------------------------------------------------------------


def deployment(cell: Cell) -> dict:
    return {**cell.config["deployment"], **cell.mix.get("deployment", {})}


def build_cluster(cell: Cell, params, seed: int, tracer=None):
    from repro.core.types import AdapterInfo
    from repro.serving import EngineBackend, LoRAServeCluster
    cfg = cell.config
    dep = deployment(cell)
    ranks = generator.adapters_of(cell.mix)
    item = F.DTYPE_BYTES[cfg["precision"]["lora_banks"]]
    infos = [AdapterInfo(a, r,
                         F.adapter_params(cell.arch.target_dims, cfg, r)
                         * item) for a, r in ranks.items()]
    backend = EngineBackend(cell.arch.model_config(cfg), params,
                            dep["servers"],
                            max_batch=dep["max_batch"],
                            max_len=dep["max_len"], seed=seed)
    return LoRAServeCluster(backend, infos, policy=dep["policy"],
                            rebalance_period=dep["rebalance_period_s"],
                            seed=cell.mix["shape_seed"], tracer=tracer)


def warm_shapes(cluster, lengths) -> None:
    """Every program the traffic can ask of each engine's bank: the
    engine compiles a prefill (and its small helpers) per prompt length
    and per number of same-length prompts admitted together, so each
    length is prefilled at every group size up to ``max_batch``, each
    followed by a decode. A group the chip has no memory for is
    skipped: traffic that made it would fail the run in any case."""
    from repro.core.request import ServeRequest
    rid = -1
    for eng in cluster.backend.engines:
        if eng is None:
            continue
        aid = eng.adapter_ids[0]
        for n in sorted(lengths):
            for group in range(1, eng.max_batch + 1):
                reqs = []
                for _ in range(group):
                    reqs.append(ServeRequest(
                        req_id=rid, adapter_id=aid,
                        rank=eng.adapter_ranks[aid], prompt_len=n,
                        output_len=2, prompt=[1] * n))
                    rid -= 1
                    eng.submit(reqs[-1])
                try:
                    eng.step()
                except jax.errors.JaxRuntimeError as e:
                    if "RESOURCE_EXHAUSTED" not in str(e):
                        raise
                    log(f"warm-up: no memory for {group} prompts of {n}")
                for req in reqs:
                    eng.cancel(req.req_id)
        eng.drain_completed()


def decode_hlo(cluster, scopes) -> Dict[str, str]:
    """Instruction name -> scope over each engine's compiled decode
    program (found in the compile cache once ``warm_shapes`` ran)."""
    from . import scopes as S
    hlo: Dict[str, str] = {}
    clash = 0
    for eng in cluster.backend.engines:
        if eng is None:
            continue
        with eng._ctx():
            text = eng._decode.lower(
                eng.params, eng.cache, eng.last_token, eng.bank,
                eng._slot_lora).compile().as_text()
        got = S.hlo_scopes(text, scopes)
        clash += sum(1 for k, v in got.items() if hlo.get(k, v) != v)
        hlo.update(got)
    if clash:
        log(f"trace: {clash} decode instructions named alike in two "
            f"engines' programs take different scopes")
    return hlo


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------


class Profiler:
    """A device trace of the window's last seconds, with the host's
    record of each engine step in it for the rooflines."""

    def __init__(self, cell: Cell, peak: dict, tmpdir: str):
        self.cell, self.peak, self.dir = cell, peak, tmpdir
        self.on = False
        self.done = False
        self.t0 = self.t1 = None
        self.mark_mono_ns = None
        self.model_flops = 0
        self.decode_least_s = 0.0
        self.decode_steps = 0
        self.scope_least_s: Dict[str, float] = {}
        self.hlo: Dict[str, str] = {}   # decode instruction -> scope
        self.host: List[tuple] = []      # (label, mono_start, mono_end)

    def start(self):
        jax.profiler.start_trace(self.dir)
        self.mark_mono_ns = time.monotonic_ns()
        with jax.profiler.TraceAnnotation("chipbench.mark"):
            pass
        self.t0 = time.monotonic()
        self.on = True

    def stop(self):
        self.t1 = time.monotonic()
        self.on = False
        self.done = True
        jax.profiler.stop_trace()

    def snapshot(self, cluster) -> dict:
        snap = {}
        for e, eng in enumerate(cluster.backend.engines):
            if eng is not None:
                snap[e] = {r.req_id: len(r.output)
                           for r in eng.slots if r is not None}
        return snap

    def account(self, cluster, before: dict, finished) -> None:
        """Price each engine's work in the poll that just ran."""
        cfg, arch = self.cell.config, self.cell.arch
        done_by = {}
        for r in finished:
            done_by.setdefault(r.server, []).append(r)
        for e, eng in enumerate(cluster.backend.engines):
            if eng is None:
                continue
            seen = before.get(e, {})
            rows = [r for r in eng.slots if r is not None]
            rows += done_by.get(e, [])
            pre, dec = [], []
            for r in rows:
                got = len(r.output) - seen.get(r.req_id, 0)
                if r.req_id not in seen:
                    pre.append((r.adapter_id, r.rank, len(r.prompt)))
                    got -= 1
                if got > 0:
                    dec.append((r.adapter_id, r.rank,
                                len(r.prompt) + len(r.output) - 1))
            if pre:
                self.model_flops += arch.prefill_flops(cfg, pre)
            if dec:
                fl, nb = arch.decode_cost(cfg, dec)
                self.model_flops += fl
                self.decode_least_s += F.least_seconds(fl, nb, self.peak)
                self.decode_steps += 1
                for scope, (fl, nb) in arch.scope_cost(cfg, dec).items():
                    self.scope_least_s[scope] = self.scope_least_s.get(
                        scope, 0.0) + F.least_seconds(fl, nb, self.peak)

    def reduce(self, cluster, tracer) -> Optional[dict]:
        from . import scopes as S
        from . import trace as T
        kept = S.load(self.dir, self.hlo)
        if kept["mark_ns"] is None:
            log("trace: no chipbench.mark event; cannot align clocks")
            return None
        off = kept["mark_ns"] - self.mark_mono_ns
        lo = kept["mark_ns"]
        hi = lo + (self.t1 - self.t0) * 1e9
        spans = []
        if tracer is not None:
            t0 = cluster.backend._t0
            for s in tracer.spans:
                if s.cat == "iteration":
                    spans.append((f"{s.name} {s.track}",
                                  (t0 + s.start) * 1e9 + off,
                                  (t0 + s.end) * 1e9 + off))
        spans += [(n, a * 1e9 + off, b * 1e9 + off) for n, a, b in self.host]
        red = T.reduce(kept, (lo, hi), spans)
        if red is None:
            return None
        red.update(model_flops=self.model_flops,
                   decode_least_s=self.decode_least_s,
                   decode_steps=self.decode_steps,
                   decode_device_s=T.module_seconds(red, S.DECODE),
                   scope_device_s=S.decode_scopes(kept, (lo, hi)),
                   scope_least_s=dict(self.scope_least_s),
                   span_s=self.t1 - self.t0, peak_flops=self.peak["flops"])
        log(f"trace: {kept['unnamed']} op events outside the decode "
            f"program's HLO; decode device s by scope "
            + " ".join(f"{k}={v:.4f}"
                       for k, v in sorted(red["scope_device_s"].items())))
        return red


def drive(cell: Cell, cluster, plans, *, seconds: float, counter,
          profiler: Optional[Profiler] = None, t_start: float = 0.0):
    """Serve the schedule: warm-up, the window of ``seconds``, then the
    drain of the window's requests. Returns the pieces of a ``Run``."""
    from repro.core.request import ServeRequest
    mix = cell.mix
    backlog = mix["arrivals"]["kind"] == "backlog"
    drain_limit = float(mix["drain_limit_s"])
    win0 = float(mix["warmup_s"])
    win1 = win0 + seconds
    engines = cluster.backend.engines
    reqs: Dict[int, object] = {}
    submit_t: Dict[int, float] = {}
    ended: Dict[int, float] = {}        # finished or timed out
    finished = set()
    marks: Dict[str, dict] = {}
    cancelled = set()
    polls: List[float] = []             # each poll's host time in the window
    stalls: List[str] = []              # the window's polls over STALL_S
    i, n = 0, len(plans)

    def mark(now):
        return {"t": now, "compiles": counter.compiles,
                "cache_hits": counter.hits,
                "bank_rebuilds": sum(e.bank_rebuilds for e in engines
                                     if e is not None),
                "tokens_decoded": sum(e.tokens_decoded for e in engines
                                      if e is not None)}

    def host(label, fn, *args):
        """``fn(*args)``, on the traced span's record of host work."""
        if profiler is None or not profiler.on:
            return fn(*args)
        t = time.monotonic()
        try:
            return fn(*args)
        finally:
            profiler.host.append((label, t, time.monotonic()))

    while True:
        now = cluster.clock()
        if "start" not in marks and now >= win0:
            marks["start"] = mark(now)
            setup_s = time.monotonic() - t_start
            win0, win1 = now, now + seconds
        if (profiler is not None and "start" in marks and "end" not in marks
                and not profiler.done and not profiler.on
                and now >= win1 - TRACE_SECONDS):
            profiler.start()
        while i < n and plans[i].t <= now:
            p = plans[i]
            req = ServeRequest(req_id=p.idx, adapter_id=p.adapter_id,
                               rank=p.rank, prompt_len=p.prompt_len,
                               output_len=p.output_len, arrival=p.t,
                               prompt=list(p.prompt))
            host("submit", cluster.submit, req, now)
            reqs[p.idx] = req
            submit_t[p.idx] = now
            i += 1
        before = profiler.snapshot(cluster) if profiler and profiler.on \
            else None
        t_poll = time.monotonic()
        ru = resource.getrusage(resource.RUSAGE_SELF)
        events = host("poll", cluster.poll)
        if "start" in marks and "end" not in marks:
            polls.append(time.monotonic() - t_poll)
            if polls[-1] > STALL_S:
                ru1 = resource.getrusage(resource.RUSAGE_SELF)
                stalls.append(
                    f"{polls[-1]:.3f} s at {t_poll - t_start:.3f} s of "
                    f"the run, wall {time.time() - polls[-1]:.3f}, "
                    f"major faults {ru1.ru_majflt - ru.ru_majflt}, "
                    f"involuntary switches {ru1.ru_nivcsw - ru.ru_nivcsw}")
        for ev in events:
            if ev.kind in ("finish", "timeout"):
                ended[ev.req.req_id] = ev.now
            if ev.kind == "finish":
                finished.add(ev.req.req_id)
        if before is not None:
            profiler.account(cluster, before,
                             [e.req for e in events if e.kind == "finish"])
        now = cluster.clock()
        if "start" in marks and "end" not in marks and now >= win1:
            marks["end"] = mark(now)
            if profiler is not None and profiler.on:
                profiler.stop()
            if backlog:
                # what the window never admitted is not attempted
                for rid, r in reqs.items():
                    if r.prefill_start < 0 and rid not in ended \
                            and cluster.cancel_request(rid):
                        cancelled.add(rid)
        if "end" in marks:
            if backlog:
                waiting = [rid for rid in reqs
                           if rid not in cancelled and rid not in ended]
            else:
                waiting = [p.idx for p in plans
                           if win0 <= p.t < win1 and p.idx not in ended]
            if not waiting or now >= win1 + drain_limit:
                give_up = now
                break
        if cluster.pending() == 0 and i < n:
            host("sleep", time.sleep, min(max(0.0, plans[i].t - now), 0.005))

    if polls:
        log(f"window polls: {len(polls)}, median "
            f"{1e3 * percentile(polls, 50):.2f} ms, p99 "
            f"{1e3 * percentile(polls, 99):.2f} ms, max "
            f"{1e3 * max(polls):.2f} ms, {sum(polls):.2f} s in all")
    for line in stalls:
        log(f"window poll stalled: {line}")
    if backlog:
        # served inside the window: admitted, and not done before it
        measured = [rid for rid, r in reqs.items() if rid not in cancelled
                    and r.prefill_start >= 0
                    and ended.get(rid, win1) >= win0]
    else:
        measured = [p.idx for p in plans if win0 <= p.t < win1]
    by_idx = {p.idx: p for p in plans}
    out = []
    for rid in measured:
        p, r = by_idx[rid], reqs.get(rid)
        rec = Req(rid, p.adapter_id, p.rank, p.t,
                  submit_t.get(rid, math.inf), p.prompt_len, p.output_len)
        if r is not None:
            rec.prefill_start = r.prefill_start if r.prefill_start >= 0 \
                else None
            rec.t_first = r.t_first_token
            rec.finished = rid in finished
            rec.t_finish = r.t_finish if rec.finished else None
            rec.n_out = len(r.output)
        out.append(rec)
    first_in_window = sum(1 for r in reqs.values()
                          if r.t_first_token is not None
                          and win0 <= r.t_first_token < win1)
    s, e = marks["start"], marks["end"]
    return {
        "setup_s": setup_s, "window": (s["t"], e["t"]), "give_up": give_up,
        "requests": out,
        "served": {rid: reqs[rid] for rid in finished if rid in reqs},
        "tokens_in_window": e["tokens_decoded"] - s["tokens_decoded"]
        + first_in_window,
        "counters": {k: e[k] - s[k] for k in s if k != "t"},
    }


# ---------------------------------------------------------------------------
# the check against the plain reference
# ---------------------------------------------------------------------------


def sample_served(cell: Cell, served: list, seed: int) -> list:
    """Finished requests to compare, drawn from the seed: the longest,
    then others until ``check.served_tokens`` tokens are in."""
    if not served:
        return []
    want = int(cell.mix["check"]["served_tokens"])
    longest = max(served, key=lambda r: (len(r.prompt) + len(r.output),
                                         r.req_id))
    rest = sorted((r for r in served if r is not longest),
                  key=lambda r: r.req_id)
    random.Random(seed).shuffle(rest)
    out, total = [longest], len(longest.output)
    for r in rest:
        if total >= want:
            break
        out.append(r)
        total += len(r.output)
    return out


@jax.jit
def gaps(ref_logits, ids):
    """How far the logit of ``ids`` (S,) lies below the reference's best
    at each position."""
    picked = jnp.take_along_axis(ref_logits, ids[:, None], axis=-1)[:, 0]
    return jnp.max(ref_logits, axis=-1) - picked


def compare(cell: Cell, seed: int, sample, controls=(), refs=None) -> dict:
    """Widest gap by which a served token's logit lies below the plain
    reference's best, over ``sample`` [(adapter, rank, prompt, output)],
    and at how many positions the served token is not the reference's
    first (``flips``). The reference multiplies at HIGHEST precision;
    ``refs`` lists other matmul precisions to read against as well
    (``by_ref``). For each precision in ``controls`` the same two
    numbers of the tokens that the reference computed in that precision
    puts first at the same positions (``control``)."""
    import numpy as np
    t0 = time.monotonic()
    cfg, arch = cell.config, cell.arch
    stated = REFERENCE_MATMUL
    refs = (stated,) + tuple(r for r in (refs or ()) if r != stated)
    max_len = deployment(cell)["max_len"]
    params = weights.make_params(arch, cfg, seed)
    by_ref = {m: {"widest_gap": 0.0, "flips": 0,
                  "control": {c: {"widest_gap": 0.0, "flips": 0}
                              for c in controls}} for m in refs}

    def tally(into, g):
        into["widest_gap"] = max(into["widest_gap"], float(g.max()))
        into["flips"] += int((g > 0).sum())

    n_tok = 0
    for aid, rank, prompt, output in sample:
        ad = weights.make_adapter(arch, cfg, seed, aid, rank)
        seq = list(prompt) + list(output[:-1])
        toks = np.zeros(max_len, np.int32)
        toks[:len(seq)] = seq
        tgt = np.zeros(max_len, np.int32)
        P = len(prompt)
        tgt[P - 1:P - 1 + len(output)] = output
        sl = slice(P - 1, P - 1 + len(output))
        n_tok += len(output)
        low = {}
        for c in controls:
            lg = arch.logits(cfg, params, ad, toks, precision=c)
            low[c] = jnp.argmax(lg, axis=-1).astype(jnp.int32)
            del lg
        for m in refs:
            ref = arch.logits(cfg, params, ad, toks, matmul=m)
            tally(by_ref[m], np.asarray(gaps(ref, jnp.asarray(tgt)))[sl])
            for c in controls:
                tally(by_ref[m]["control"][c],
                      np.asarray(gaps(ref, low[c]))[sl])
            del ref
        del ad, low
    log(f"reference over {len(sample)} requests, {n_tok} tokens: "
        f"{time.monotonic() - t0:.1f}s")
    out = {"widest_gap": by_ref[stated]["widest_gap"],
           "flips": by_ref[stated]["flips"], "tokens": n_tok}
    if controls:
        out["control"] = by_ref[stated]["control"]
    if len(refs) > 1:
        out["by_ref"] = by_ref
    return out


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def device_info(devices) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    d0 = devices[0]
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def serve(cell: Cell, seed: int, seconds: float, *, trace: bool = False,
          t_start: Optional[float] = None, tmpdir: Optional[str] = None,
          break_program=None):
    """Set up and drive one run of ``cell``. Returns (Run, sample,
    device dict, answers of the wrong length). ``break_program`` (tests)
    is a context manager entered around the served path to plant a fault
    in it. Off the TPU there is no peak table, so no device trace."""
    import contextlib
    t_start = time.monotonic() if t_start is None else t_start
    counter = CompileCounter()
    cfg = cell.config
    devices = jax.devices()[:cell.chips]
    peak = peaks(devices[0].device_kind) \
        if devices[0].platform == "tpu" else None
    params = weights.make_params(cell.arch, cfg, seed)
    jax.block_until_ready(params)
    dep = deployment(cell)
    plans = generator.schedule(cell.mix, seed=seed,
                               warmup_s=float(cell.mix["warmup_s"]),
                               window_s=seconds,
                               vocab_size=cfg["vocab_size"],
                               block_s=float(cell.mix["reorder_block_s"]))
    tracer = None
    if trace:
        from repro.obs import Tracer
        tracer = Tracer()
    profiler = Profiler(cell, peak, tmpdir) if (trace and peak) else None
    fault = break_program() if break_program else contextlib.nullcontext()
    with weights.served_adapters(cell.arch, cfg, seed), fault:
        cluster = build_cluster(cell, params, seed, tracer)
        warm_shapes(cluster, {p.prompt_len for p in plans})
        if profiler is not None:
            profiler.hlo = decode_hlo(cluster, cell.arch.SCOPES)
        log(f"set-up before traffic {time.monotonic() - t_start:.1f}s, "
            f"compiles={counter.compiles} cache_hits={counter.hits}")
        got = drive(cell, cluster, plans, seconds=seconds, counter=counter,
                    profiler=profiler, t_start=t_start)
    run = Run(cell.name, cell.mix, got["setup_s"], got["window"],
              got["give_up"], got["requests"], got["tokens_in_window"],
              got["counters"])
    log("in the window: " + " ".join(f"{k}={v}"
                                     for k, v in run.counters.items()))
    if tracer is not None:
        w0, w1 = run.window
        run.decode_spans = [s.end - s.start for s in tracer.spans
                            if s.name == "decode" and s.cat == "iteration"
                            and w0 <= s.start < w1]
    if profiler is not None and profiler.done:
        run.profile = profiler.reduce(cluster, tracer)
    measured = {r.idx for r in run.requests if r.finished}
    served = [r for rid, r in got["served"].items() if rid in measured]
    sample = [(r.adapter_id, r.rank, list(r.prompt), list(r.output))
              for r in sample_served(cell, served, seed)]
    short = sum(1 for r in served
                if len(r.output) != min(r.output_len,
                                        dep["max_len"] - len(r.prompt)))
    dev = device_info(devices)
    # free the program's state before the reference runs
    del cluster, params, got, served, plans, tracer, profiler
    gc.collect()
    return run, sample, dev, short


def checks(cell: Cell, seed: int, sample, short: int) -> dict:
    """Each number compared, beside its limit."""
    limit = float(cell.mix["check"]["gap_limit"])
    # with nothing to compare, tokens_compared fails the run
    got = compare(cell, seed, sample) if sample else {"widest_gap": 0.0,
                                                      "tokens": 0}
    return {
        "widest_logit_gap": {"value": got["widest_gap"], "limit": limit},
        "tokens_compared": {"value": got["tokens"],
                            "limit": int(cell.mix["check"]["served_tokens"]
                                         ) // 2},
        "wrong_length_answers": {"value": short, "limit": 0},
    }


def passed(chk: dict) -> bool:
    return (chk["widest_logit_gap"]["value"]
            <= chk["widest_logit_gap"]["limit"]
            and chk["tokens_compared"]["value"]
            >= chk["tokens_compared"]["limit"]
            and chk["wrong_length_answers"]["value"]
            <= chk["wrong_length_answers"]["limit"])


def result(cell: Cell, run: Run, dev: dict, chk: dict, trace: bool) -> dict:
    names = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in names:
        v = reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    out = {
        "correct": passed(chk),
        "attempted": len(run.requests),
        "failed": sum(1 for r in run.requests if not r.finished),
        "metrics": metrics,
        "device": dict(dev),
    }
    if trace:
        prof = run.profile or {}
        out["device"]["busy_s"] = prof.get("busy_s")
        out["device"]["window_s"] = prof.get("window_s")
        if prof:
            out["breakdown"] = {"device_ops": prof["device_ops"],
                                "idle_gaps": prof["idle_gaps"]}
    out["checks"] = chk
    return out


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: Optional[float] = None, **kw) -> dict:
    """One run, checked, as the result line's object; ``kw`` goes to
    ``serve``."""
    import tempfile
    with tempfile.TemporaryDirectory(prefix="chipbench-trace-") as tmp:
        run, sample, dev, short = serve(cell, seed, seconds, trace=trace,
                                        t_start=t_start, tmpdir=tmp, **kw)
    chk = checks(cell, seed, sample, short)
    res = result(cell, run, dev, chk, trace)
    for k, v in chk.items():
        log(f"check {k}: {v['value']} (limit {v['limit']})")
    return res
