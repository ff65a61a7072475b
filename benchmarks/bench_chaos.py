"""Chaos harness — kill / flap / stall under live traffic on the
simulated cluster, measuring what the recovery machinery actually buys.

Each scenario replays the *same* trace twice: once fault-free
(baseline) and once with a seeded ``FaultPlan``. Reported per scenario:

- **recovery time** — crash -> confirmed-dead (one detector window) and
  crash -> survivors re-placed + stranded work re-dispatched, from the
  run's ``RecoveryRecord``s;
- **SLO dip / restore** — windowed TTFT attainment bucketed by arrival
  into pre-fault / fault / post-restore windows. Loss-free recovery
  means the post window returns to the pre-fault level;
- **retried vs lost** — re-dispatched continuations, fetch retries /
  timeouts, circuit-breaker opens, and the number of requests that
  never finished (must be 0: a crash may slow requests, never eat them).

Both scenario sets run ``LoRAServeCluster`` over ``SimBackend`` (fault
injector + heartbeat ``FailureDetector`` on the virtual clock): "sim"
rows rebalance periodically on a shifting trace, "facade" rows keep
placement static on a lighter one.
"""
from __future__ import annotations

import copy

from repro.faults import FaultPlan
from repro.traces import make_adapters, synth_trace

from .common import emit, sim_cluster

# tight enough that a crash's detection window + re-queue visibly
# dents the fault-window attainment, loose enough that the healthy
# baseline sits at ~1.0 (sim TTFTs are tens of milliseconds)
SLO_TTFT = 0.25


# ---------------------------------------------------------------------
# shared metric helpers (every run reduces to (arrival, ok) pairs)
# ---------------------------------------------------------------------
def _pairs(report):
    """(arrival, finished, ttft) per request of a ClusterReport."""
    return [(r.arrival, r.finished, r.ttft) for r in report.results]


def _attainment(pairs, t0=0.0, t1=float("inf")):
    w = [(fin, t) for (a, fin, t) in pairs if t0 <= a < t1]
    if not w:
        return 1.0
    return sum(1 for fin, t in w
               if fin and t is not None and t <= SLO_TTFT) / len(w)


def _lost(pairs):
    return sum(1 for _, fin, _ in pairs if not fin)


def _recovery(records, t_kill):
    """(detect_s, recover_s) — crash->confirmed and crash->recovered,
    worst case over records (one kill => one record)."""
    if not records:
        return float("nan"), float("nan")
    det = max(r.detected_at - t_kill for r in records)
    rec = max(r.recovered_at - t_kill for r in records)
    return det, rec


def _windows_derived(pairs, t_kill, t_restore):
    pre = _attainment(pairs, 0.0, t_kill)
    dip = _attainment(pairs, t_kill, t_restore)
    post = _attainment(pairs, t_restore)
    return (f"slo_pre={pre:.4f};slo_fault={dip:.4f};slo_post={post:.4f};"
            f"slo_restored={int(post >= pre - 1e-9)}", pre, post)


# ---------------------------------------------------------------------
# "sim" scenarios: periodic rebalancing under drift
# ---------------------------------------------------------------------
def _sim(adapters, plan=None, window=0.5):
    # periodic rebalances + a shifting trace keep adapter transfers in
    # flight throughout the run, so link/stall faults have something to
    # bite; timeouts are off so any lost request is the chaos plane's
    # fault, not the reaper's
    return sim_cluster(
        3, adapters, timeout=1e9, policy="loraserve", seed=7,
        rebalance_period=6.0, prefetch=True, fault_plan=plan,
        detector_window=window, durable_ssd=True)


def _sim_rows(rows, fast):
    n_adapters = 8 if fast else 24
    duration = 30.0 if fast else 90.0
    rps = 14.0 if fast else 20.0
    t_kill, t_restore = duration / 3, 2 * duration / 3
    window = 0.5
    adapters = make_adapters(n_adapters, seed=3)
    trace = synth_trace(adapters, rps=rps, duration=duration,
                        popularity="shifting", prompt_len=128,
                        output_len=64, seed=11)

    base = _sim(adapters).run(copy.deepcopy(trace))
    base_pairs = _pairs(base)
    rows.append(emit(
        "chaos/sim/baseline", 0.0,
        f"requests={len(trace)};completed={len(trace) - _lost(base_pairs)};"
        f"lost={_lost(base_pairs)};"
        f"slo_attainment={_attainment(base_pairs):.4f}"))

    # kill-a-server: crash at T/3, restore at 2T/3, everything in
    # flight on the victim re-dispatched from its last emitted token
    res = _sim(adapters, FaultPlan.kill_one(t_kill, 0, t_restore),
               window).run(copy.deepcopy(trace))
    pairs = _pairs(res)
    det, rec = _recovery(res.recovery_records, t_kill)
    win, _, _ = _windows_derived(pairs, t_kill, t_restore)
    rows.append(emit(
        "chaos/sim/kill-one", rec * 1e6,
        f"detect_s={det:.3f};recover_s={rec:.3f};"
        f"detector_window_s={window};failures={res.server_failures};"
        f"recoveries={res.recoveries};redispatched={res.redispatched};"
        f"lost={_lost(pairs)};{win};"
        f"fetch_retries={res.fetch_retries};"
        f"fetch_timeouts={res.fetch_timeouts};"
        f"breaker_opens={res.breaker_opens}"))

    # link flap: server 0's egress NIC goes dark mid-run; fetches that
    # would source from it are excluded and pick an alternate peer/tier
    res = _sim(adapters, FaultPlan.link_flap(t_kill, 0, t_restore),
               window).run(copy.deepcopy(trace))
    pairs = _pairs(res)
    win, _, _ = _windows_derived(pairs, t_kill, t_restore)
    rows.append(emit(
        "chaos/sim/link-flap", 0.0,
        f"lost={_lost(pairs)};{win};fetches={res.fetches};"
        f"prefetches={res.prefetches};fetch_retries={res.fetch_retries};"
        f"fetch_timeouts={res.fetch_timeouts};"
        f"breaker_opens={res.breaker_opens}"))

    # stalled recovery transfer: crash a server, then silently hang the
    # re-placement prefetches launched at detection — each stalled
    # transfer must blow its per-attempt deadline, back off, and
    # relaunch from a surviving source (the timeout/retry/alternate
    # path end to end, still loss-free)
    plan = FaultPlan.kill_one(t_kill, 0, t_restore)
    t_rec = t_kill + window
    for i in range(4):
        plan = FaultPlan(plan.events +
                         FaultPlan.stall(t_rec + 0.002 * (i + 1)).events)
    res = _sim(adapters, plan, window).run(copy.deepcopy(trace))
    pairs = _pairs(res)
    win, _, _ = _windows_derived(pairs, t_kill, t_restore)
    rows.append(emit(
        "chaos/sim/kill-stall-fetch", 0.0,
        f"lost={_lost(pairs)};{win};redispatched={res.redispatched};"
        f"fetch_retries={res.fetch_retries};"
        f"fetch_timeouts={res.fetch_timeouts};"
        f"breaker_opens={res.breaker_opens}"))
    return pairs is not None


# ---------------------------------------------------------------------
# "facade" scenarios: static placement, lighter traffic
# ---------------------------------------------------------------------
def _facade(adapters, plan=None, window=0.5):
    return sim_cluster(3, adapters, rebalance_period=1e9, seed=7,
                       prefetch=True, fault_plan=plan,
                       detector_window=window, durable_ssd=True)


def _facade_rows(rows, fast):
    n_adapters = 6 if fast else 16
    duration = 20.0 if fast else 60.0
    rps = 4.0 if fast else 8.0
    t_kill, t_restore = duration / 3, 2 * duration / 3
    window = 0.5
    adapters = make_adapters(n_adapters, seed=5)
    trace = synth_trace(adapters, rps=rps, duration=duration,
                        prompt_len=128, output_len=32, seed=13)

    base = _facade(adapters).run(copy.deepcopy(trace))
    base_pairs = _pairs(base)
    rows.append(emit(
        "chaos/facade/baseline", 0.0,
        f"requests={len(trace)};lost={_lost(base_pairs)};"
        f"slo_attainment={_attainment(base_pairs):.4f}"))

    report = _facade(adapters,
                     FaultPlan.kill_one(t_kill, 0, t_restore),
                     window).run(copy.deepcopy(trace))
    pairs = _pairs(report)
    det, rec = _recovery(report.recovery_records, t_kill)
    win, _, _ = _windows_derived(pairs, t_kill, t_restore)
    rows.append(emit(
        "chaos/facade/kill-one", rec * 1e6,
        f"detect_s={det:.3f};recover_s={rec:.3f};"
        f"detector_window_s={window};"
        f"failures={report.server_failures};"
        f"recoveries={report.recoveries};"
        f"redispatched={report.redispatched};lost={_lost(pairs)};{win};"
        f"fetch_retries={report.fetch_retries};"
        f"fetch_timeouts={report.fetch_timeouts};"
        f"breaker_opens={report.breaker_opens}"))
    return True


def run(fast: bool = True):
    rows = []
    _sim_rows(rows, fast)
    _facade_rows(rows, fast)

    # headline: loss-free in both scenario sets, SLO restored post-fault
    def field(name, key):
        for n, _, derived in rows:
            if n == name:
                for kv in derived.split(";"):
                    k, _, v = kv.partition("=")
                    if k == key:
                        return float(v)
        return float("nan")

    loss_free = (field("chaos/sim/kill-one", "lost") == 0.0
                 and field("chaos/facade/kill-one", "lost") == 0.0)
    restored = (field("chaos/sim/kill-one", "slo_restored") == 1.0
                and field("chaos/facade/kill-one", "slo_restored") == 1.0)
    rows.append(emit(
        "chaos/headline", 0.0,
        f"kill_one_loss_free_both={int(loss_free)};"
        f"slo_restored_both={int(restored)};"
        f"sim_recover_s={field('chaos/sim/kill-one', 'recover_s'):.3f};"
        f"facade_recover_s="
        f"{field('chaos/facade/kill-one', 'recover_s'):.3f}"))
    return rows
