#!/usr/bin/env python3
"""Find an open-loop cell's knee: serve its mix at several fixed rates,
one after the other in this process, and print per rate what finished,
the tails, and the queue left at the window's close.

    python3 chipbench/sweep.py --workload <cell> --rates 1,2,3 \
        --seconds 20 --seed <n>

It is run once, when a cell's rate is chosen, and never by the
benchmark's own runs. Needs a TPU, as ``run.py`` does.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from chipbench import harness
    from chipbench.run import setup_jax
    if not setup_jax(1):
        return 2
    base = harness.load_cell(args.workload, ROOT)
    for rate in (float(r) for r in args.rates.split(",")):
        cell = copy.deepcopy(base)
        cell.mix["arrivals"]["rate_rps"] = rate
        t0 = time.monotonic()
        run, _, dev, _ = harness.serve(cell, args.seed, args.seconds,
                                       t_start=t0)
        reqs = run.requests
        done = [r for r in reqs if r.finished]
        tp = [t for t in (r.tpot() for r in reqs) if t is not None]
        print(json.dumps({
            "rate_rps": rate, "scheduled": len(reqs),
            "finished": len(done),
            "ttft_p50_s": harness.percentile(
                [r.ttft(run.give_up) for r in reqs], 50),
            "ttft_p95_s": harness.percentile(
                [r.ttft(run.give_up) for r in reqs], 95),
            "tpot_p50_ms": 1e3 * (harness.percentile(tp, 50) or 0),
            "tpot_p95_ms": 1e3 * (harness.percentile(tp, 95) or 0),
            "queue_wait_p95_s": harness.percentile(
                [(r.prefill_start if r.prefill_start is not None
                  else run.give_up) - r.t_sched for r in reqs], 95),
            "drain_s": run.give_up - run.window[1],
            "output_tok_s": run.tokens_in_window
            / (run.window[1] - run.window[0]),
            "memory_peak_bytes": dev["memory_peak_bytes"],
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
