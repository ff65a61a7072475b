"""From a profiler trace to device numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and keeps
a small JSON-able form: the device planes' op and module events, and
the host's ``chipbench.mark`` event that ties the profiler's clock to
the host's monotonic clock. ``reduce`` turns that form, the profiled
span and the host's own records into busy time, idle gaps and module
times. The reduction runs on the kept form only, so a recorded trace
(``chipbench/tests/data``) checks it without a chip.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Tuple

MARK = "chipbench.mark"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def module_name(event_name: str) -> str:
    """``jit__decode(1234)`` -> ``jit__decode``."""
    return event_name.split("(", 1)[0].strip()


def load(trace_dir: str, label=None) -> dict:
    """The kept form of the newest trace under ``trace_dir``. Each op
    keeps ``label(event name)`` as its name, or none: busy time needs
    only the intervals, and the HLO text that names an op is long."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    out = {"devices": [], "mark_ns": None}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = {}
            for line in plane.lines:
                if line.name == OPS_LINE:
                    lines[OPS_LINE] = [
                        [label(ev.name) if label else "", ev.start_ns,
                         ev.duration_ns] for ev in line.events]
                elif line.name == MODULES_LINE:
                    lines[MODULES_LINE] = [
                        [ev.name, ev.start_ns, ev.duration_ns]
                        for ev in line.events]
            out["devices"].append({"name": plane.name, "lines": lines})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == MARK and out["mark_ns"] is None:
                        out["mark_ns"] = ev.start_ns
    out["devices"] = [d for d in out["devices"] if d["lines"]]
    return out


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1] = (merged[-1][0], b)
        else:
            merged.append((a, b))
    return merged


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def reduce(kept: dict, span_ns: Tuple[float, float],
           host_spans: List[Tuple[str, float, float]] = (),
           top: int = 10) -> Optional[dict]:
    """Device numbers over ``span_ns`` (profiler clock).

    ``host_spans`` are (label, start_ns, end_ns) on the profiler clock,
    innermost first where they nest; an idle gap is labelled with the
    first that holds its midpoint, else "host". Returns None where the
    trace holds no device events."""
    lo, hi = span_ns
    devs = kept["devices"]
    if not devs:
        return None
    busy_per_dev, gaps = [], []
    modules: Dict[str, float] = {}
    for dev in devs:
        ops = dev["lines"].get(OPS_LINE) or dev["lines"].get(MODULES_LINE)
        iv = _union(_clip([(s, s + d) for _, s, d in ops], lo, hi))
        busy_per_dev.append(sum(b - a for a, b in iv))
        edges = [lo] + [x for ab in iv for x in ab] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((a, b))
        for name, s, d in dev["lines"].get(MODULES_LINE, []):
            a, b = max(s, lo), min(s + d, hi)
            if b > a:
                m = module_name(name)
                modules[m] = modules.get(m, 0.0) + (b - a) * 1e-9
    window_s = (hi - lo) * 1e-9
    busy_s = sum(busy_per_dev) / len(busy_per_dev) * 1e-9

    def label(a, b):
        mid = 0.5 * (a + b)
        for name, s, e in host_spans:
            if s <= mid < e:
                return name
        return "host"

    gaps.sort(key=lambda ab: ab[1] - ab[0], reverse=True)
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "modules_s": modules,
        "device_ops": sorted(([m, s] for m, s in modules.items()),
                             key=lambda x: -x[1])[:top],
        "idle_gaps": [[label(a, b), (b - a) * 1e-9] for a, b in gaps[:top]],
    }


def module_seconds(reduced: dict, name: str) -> Optional[float]:
    """Device seconds of the module ``name`` in the span, or None."""
    if reduced is None:
        return None
    s = reduced["modules_s"].get(name)
    return s if s else None
