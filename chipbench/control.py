#!/usr/bin/env python3
"""Readings that the limit on ``widest_logit_gap`` is set from.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3 \
        --seconds 20

For each seed, in this one process: serve a window of the cell at its
own load and sizes, then read over the same sample of served requests
(a) the program's widest gap below the float32 reference's best logit
and the count of positions where the served token is not the
reference's first (the lower readings) and (b) the same of the tokens
that the reference computed in a lower precision puts first at the
same positions: bfloat16 storage, and float8 operands of every linear
layer (the control; its smallest reading is the upper one). Each
against the reference at HIGHEST precision, the one the check uses,
and, in ``by_ref``, at the TPU's default precision, the one the
configuration states for the program.
One JSON line per seed. The benchmark's own runs never run the control.
Needs a TPU, as ``run.py`` does.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from chipbench import harness
    from chipbench.run import setup_jax
    if not setup_jax(1):
        return 2
    cell = harness.load_cell(args.workload, ROOT)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.monotonic()
        run, sample, _, short = harness.serve(cell, seed, args.seconds,
                                              t_start=t0)
        got = harness.compare(cell, seed, sample,
                              controls=("bfloat16", "float8"),
                              refs=("default",))
        got.update(seed=seed, wrong_length_answers=short,
                   requests=len(sample), seconds=time.monotonic() - t0)
        print(json.dumps(got), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
