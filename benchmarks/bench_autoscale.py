"""Paper Fig 10 / §V headline — fewer GPUs under SLO with the closed
control loop.

Replays the drifting production trace (per-adapter Fig 10 patterns +
a diurnal aggregate load swing) against three fleets:

1. **static-max** — the over-provisioned baseline: peak-sized fleet for
   the whole run (what a fleet without autoscaling must do to hold the
   SLO through the peak);
2. **static-min** — a trough-sized fleet, showing why simply running
   fewer GPUs without a control loop breaks the SLO;
3. **autoscaled** — starts peak-sized with the ``ClusterController``
   attached: drift-triggered rebalances, scale-up on sustained SLO
   violation, drain + retire on sustained headroom.

Reported per fleet: GPU-hours (provision -> retire), SLO attainment,
P95 TTFT, and the control actions taken. The headline row is the
GPU-hour saving of the autoscaled fleet at equal-or-better attainment
than static-max.
"""
from __future__ import annotations

from repro.controlplane import (ClusterController, ControllerConfig,
                                SLOSpec)
from repro.traces import production_trace_with_meta

from .common import emit, sim_cluster

SLO_TTFT = 8.0


def _controller(min_servers: int, max_servers: int) -> ClusterController:
    return ClusterController(
        SLOSpec(ttft=SLO_TTFT, target=0.95, window=30.0),
        ControllerConfig(tick_period=5.0, min_servers=min_servers,
                         max_servers=max_servers, patience=2,
                         drain_patience=4, cooldown=25.0))


def _row(rows, name, res):
    att = res.slo_attainment(SLO_TTFT)
    rows.append(emit(
        f"autoscale/{name}", res.gpu_seconds * 1e6,
        f"gpu_hours={res.gpu_seconds / 3600.0:.4f};slo_attainment={att:.4f};"
        f"p95_ttft_s={res.p95_ttft():.3f};completed={res.completed()};"
        f"timed_out={res.timed_out};scale_ups={res.scale_ups};"
        f"drains={res.drains};retires={res.retires};"
        f"final_servers={res.final_servers};"
        f"oob_rebalances={res.controller_rebalances};"
        f"drift_events={len(res.drift_events)}"))
    return att, res.gpu_seconds


def run(fast: bool = True):
    rows = []
    n_adapters = 40 if fast else 80
    rps = 14 if fast else 20
    duration = 240 if fast else 480
    n_max, n_min = (6, 2)

    trace, meta = production_trace_with_meta(
        n_adapters, rps=rps, duration=duration, seed=5,
        load_profile="diurnal")
    rows.append(emit("autoscale/trace", 0.0,
                     f"requests={len(trace)};load_profile=diurnal;"
                     f"rps_base={rps};duration_s={duration}"))

    def sim(n, controller=None):
        return sim_cluster(
            n, meta["adapters"], timeout=60.0, policy="loraserve", seed=7,
            warmup=0.0, rebalance_period=15.0, controller=controller)

    def replay(s):
        import copy
        return s.run(copy.deepcopy(trace))

    static_max = replay(sim(n_max))
    att_max, gpu_max = _row(rows, f"static-{n_max}", static_max)

    static_min = replay(sim(n_min))
    _row(rows, f"static-{n_min}", static_min)

    auto = replay(sim(n_max, controller=_controller(n_min, n_max + 2)))
    att_auto, gpu_auto = _row(rows, "autoscaled", auto)

    saving = 1.0 - gpu_auto / gpu_max if gpu_max else 0.0
    rows.append(emit(
        "autoscale/headline", 0.0,
        f"gpu_hour_saving={saving:.4f};"
        f"attainment_auto={att_auto:.4f};attainment_static={att_max:.4f};"
        f"auto_meets_or_beats_static={int(att_auto >= att_max - 1e-9)}"))
    return rows
