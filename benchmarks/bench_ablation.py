"""Ablation of Algorithm 1's components (design rationale, §IV-A):

  * full          — rank budgets + fractional packing + leftovers + permute
  * no-demand     — rank-aware but demand-oblivious (uniform demand prior,
                    never rebalanced): isolates the value of Step 1
  * no-rank       — demand-aware but rank-oblivious: operating point of
                    the *mean* rank for every adapter (isolates Step 2's
                    rank budgets)
  * no-permute    — Step 5 disabled: measures migration churn (fetches)
"""
from __future__ import annotations

import copy

from repro.core.baselines import LoraservePolicy
from repro.core.placement import assign_loraserve
from repro.traces import make_adapters, synth_trace

from .common import emit, sim_cluster, timed


class NoDemandPolicy(LoraservePolicy):
    name = "ablate-no-demand"
    dynamic = False      # never rebalances => initial uniform prior only


class NoRankPolicy(LoraservePolicy):
    name = "ablate-no-rank"

    def place(self, ctx):
        mean_op = sum(ctx.operating_points.values()) / \
            len(ctx.operating_points)
        flat = dict(ctx.operating_points)
        for r in flat:
            flat[r] = mean_op
        ctx = copy.copy(ctx)
        ctx.operating_points = flat
        placement, self.last_stats = assign_loraserve(ctx)
        return placement


class NoPermutePolicy(LoraservePolicy):
    name = "ablate-no-permute"

    def place(self, ctx):
        ctx = copy.copy(ctx)
        ctx.prev_placement = None      # Step 5 sees no history
        placement, self.last_stats = assign_loraserve(ctx)
        return placement


def run(fast: bool = False):
    rows = []
    adapters = make_adapters(100, seed=1)
    trace = synth_trace(adapters, rps=20, duration=120 if fast else 180,
                        popularity="shifting", seed=2)
    variants = [("full", "loraserve"), ("no-demand", NoDemandPolicy()),
                ("no-rank", NoRankPolicy()),
                ("no-permute", NoPermutePolicy())]
    for name, pol in variants:
        sim = sim_cluster(4, adapters, timeout=60, policy=pol, seed=3,
                          warmup=40)
        res, us = timed(lambda: sim.run(copy.deepcopy(trace)), repeat=1)
        rows.append(emit(
            f"ablation/{name}", us,
            f"p95_ttft={res.p95_ttft():.3f}s;fetches={res.fetches};"
            f"timeout={res.timed_out}"))
    return rows
