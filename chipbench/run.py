#!/usr/bin/env python3
"""Run one cell of the chip benchmark.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``. With
``--trace 0`` the last line of standard output is the result with the
cell's end-to-end metrics; with ``--trace 1`` with its per-layer
metrics, read from a device trace of the window's last seconds. The
numbers compared against the plain reference, each beside its limit,
end standard error and the result line.

It needs a TPU: without one (or with fewer chips than the cell asks
for, or where Pallas would interpret) it exits non-zero and prints no
result.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def fail(msg: str) -> bool:
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)
    return False


def setup_jax(chips: int) -> bool:
    """Refuse anything but ``chips`` TPU chips with compiled Pallas
    kernels; then turn on the program's persistent compile cache,
    keeping every program in it. Returns False, having said why, where
    the run must not go on."""
    try:
        from repro.kernels import default_interpret
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        return fail(f"the program is not beside the benchmark ({e})")
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        return fail(f"needs a TPU; JAX found platform "
                    f"{devices[0].platform!r} ({devices[0].device_kind})")
    if len(devices) < chips:
        return fail(f"needs {chips} chips, JAX found {len(devices)}")
    if default_interpret():
        return fail("Pallas kernels would run in interpret mode")
    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    print(f"[chipbench] {devices[0].device_kind} x{len(devices)}; "
          f"compile cache at {path}", file=sys.stderr, flush=True)
    return True


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    try:
        from chipbench import harness
        cell = harness.load_cell(args.workload, ROOT)
    except (ImportError, KeyError, FileNotFoundError) as e:
        fail(str(e))
        return 2
    if not setup_jax(cell.chips):
        return 2
    res = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           t_start=T_START)
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
