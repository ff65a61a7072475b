"""Paper Fig 22 — sensitivity to rank-popularity skew: power-law alpha
in {1/3, 1, 3}, 100 adapters, 4 servers — plus the beyond-paper
padded-vs-bucketed A/B: the same trace replayed with rank-bucketed
server banks, showing the max-rank padding tax (and its elimination)
per policy."""
from __future__ import annotations

import copy

from repro.traces import make_adapters, synth_trace

from .common import emit, sim_cluster, timed

POLICIES = ["loraserve", "slora-random", "slora-contiguous"]
BANK_MODES = ["padded", "bucketed"]


def run(fast: bool = False):
    rows = []
    alphas = (1 / 3, 3.0) if fast else (1 / 3, 1.0, 3.0)
    adapters = make_adapters(100, seed=1)
    for alpha in alphas:
        trace = synth_trace(adapters, rps=20, duration=150,
                            popularity="powerlaw", alpha=alpha, seed=2)
        for pol in POLICIES:
            p95 = {}
            for mode in BANK_MODES:
                sim = sim_cluster(4, adapters, timeout=60, bank_mode=mode,
                                  policy=pol, seed=3, warmup=40)
                res, us = timed(lambda: sim.run(copy.deepcopy(trace)),
                                repeat=1)
                p95[mode] = res.p95_ttft()
                rows.append(emit(
                    f"fig22/alpha{alpha:.2f}/{pol}/{mode}", us,
                    f"p95_ttft={res.p95_ttft():.3f}s;"
                    f"timeout={res.timed_out}"))
            saved = 1.0 - p95["bucketed"] / p95["padded"] \
                if p95["padded"] > 0 else 0.0
            rows.append(emit(
                f"fig22/alpha{alpha:.2f}/{pol}/padding-tax", 0.0,
                f"p95_saving={saved:.3f}"))
    return rows
