"""Benchmark driver — one module per paper table/figure. Prints
``name,us_per_call,derived`` CSV (and tees a copy per bench under
experiments/bench/).

  PYTHONPATH=src python -m benchmarks.run [--only placement,workloads] [--full]
"""
from __future__ import annotations

import argparse
import os
import sys
import time

from . import (bench_ablation, bench_autoscale, bench_chaos,
               bench_interference, bench_kernels, bench_mesh,
               bench_placement, bench_rank_skew, bench_roofline,
               bench_scalability, bench_server, bench_transfer,
               bench_workloads)
from .common import fmt_rows

BENCHES = {
    "autoscale": bench_autoscale.run,
    "chaos": bench_chaos.run,
    "interference": lambda fast: bench_interference.run(),
    "transfer": bench_transfer.run,
    # "kernel" (the old bench_kernel.py) was folded into "kernels":
    # its padding-tax / flash-skip rows now come from padding_tax_rows()
    "kernels": bench_kernels.run,
    "mesh": bench_mesh.run,
    "placement": bench_placement.run,
    "workloads": bench_workloads.run,
    "scalability": bench_scalability.run,
    "rank_skew": bench_rank_skew.run,
    "server": bench_server.run,
    "roofline": lambda fast: bench_roofline.run(),
    "ablation": bench_ablation.run,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="comma list of bench names (default: all)")
    ap.add_argument("--full", action="store_true",
                    help="full-size sweeps (default: fast subsets)")
    ap.add_argument("--outdir", default="experiments/bench")
    args = ap.parse_args()

    names = [n for n in args.only.split(",") if n] or list(BENCHES)
    os.makedirs(args.outdir, exist_ok=True)
    all_rows = []
    for name in names:
        t0 = time.time()
        rows = BENCHES[name](not args.full)
        all_rows.extend(rows)
        csv = fmt_rows(rows)
        with open(os.path.join(args.outdir, f"{name}.csv"), "w") as f:
            f.write(csv + "\n")
        print(f"# {name} ({time.time() - t0:.1f}s)", file=sys.stderr)
    print(fmt_rows(all_rows))


if __name__ == "__main__":
    main()
