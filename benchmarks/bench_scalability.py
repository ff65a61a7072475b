"""Paper Fig 21 — weak scaling: 4/8/12 servers with proportional adapters
and traffic; LORASERVE should sustain proportional RPS under the SLO."""
from __future__ import annotations

import copy

from repro.traces import make_adapters, synth_trace

from .common import emit, sim_cluster, timed


def run(fast: bool = False):
    rows = []
    sizes = (4, 8) if fast else (4, 8, 12)
    for n in sizes:
        adapters = make_adapters(25 * n // 4, seed=1)
        rps = 5 * n
        trace = synth_trace(adapters, rps=rps, duration=120,
                            popularity="exponential", seed=2)
        sim = sim_cluster(n, adapters, timeout=60, policy="loraserve",
                          seed=3, warmup=40)
        res, us = timed(lambda: sim.run(copy.deepcopy(trace)), repeat=1)
        rows.append(emit(
            f"fig21/servers{n}/rps{rps}", us,
            f"p95_ttft={res.p95_ttft():.3f}s;timeout={res.timed_out};"
            f"slo10s={'PASS' if res.meets_slo(10.0) else 'FAIL'}"))
    return rows
