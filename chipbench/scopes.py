"""Names inside a serving step: the device time of each named scope of
the decode program, the program's own step spans as idle-gap labels,
and the per-layer numbers read from them.

The program names its device work with ``jax.named_scope`` (the
architecture module's ``SCOPES``) and its host work with ``step`` spans
of its ``repro.obs`` tracer (``poll``, ``decode.dispatch``,
``decode.sync``, ...). ``load`` keeps the form ``trace.load`` keeps,
with each op labelled by its scope, so ``trace.reduce`` reads it
unchanged. On v5e an op event carries no ``op_name`` (its stats are its
device offset and duration); its name is the op's HLO instruction, so
the scope comes from the compiled program's HLO text (``hlo_scopes``).

A traced run keeps the decode program's device time by scope
(``decode_scopes``). The readings of the program's host spans
(``gap_labels``, ``poll_p95_ms``, ``host_gap_ms``) wait for the harness
to keep the window's program spans.
"""
from __future__ import annotations

import bisect
import re
from typing import Dict, List, Optional, Tuple

from . import trace as T
from .harness import percentile

OTHER = "other"
DECODE = "jit__decode"
_HLO_LINE = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?metadata=\{[^}]*op_name="([^"]*)"')


def scope_of(op_name: str, scopes: Tuple[str, ...]) -> str:
    """The innermost of ``scopes`` (the architecture module's
    ``SCOPES``) in a ``/``-separated op_name path."""
    for part in reversed(op_name.split("/")):
        if part in scopes:
            return part
    return OTHER


def hlo_scopes(hlo_text: str, scopes: Tuple[str, ...]) -> Dict[str, str]:
    """Instruction name -> scope of ``scopes``, over every computation
    of a compiled program's HLO text."""
    out = {}
    for line in hlo_text.splitlines():
        m = _HLO_LINE.match(line)
        if m:
            out[m.group(1)] = scope_of(m.group(2), scopes)
    return out


def event_scope(name: str, hlo: Dict[str, str]) -> Optional[str]:
    """Scope of the op event ``name`` (``%fusion.12 = f32[...] ...``),
    or None where the program's HLO does not name it."""
    return hlo.get(name.lstrip("%").split(" ", 1)[0])


def load(trace_dir: str, hlo: Dict[str, str]) -> dict:
    """``trace.load``'s kept form of the newest trace under
    ``trace_dir``, each op labelled with its scope from ``hlo``
    (``hlo_scopes``); ``unnamed`` counts the ops ``hlo`` does not
    hold, labelled ``other``."""
    unnamed = 0

    def label(name):
        nonlocal unnamed
        scope = event_scope(name, hlo)
        if scope is None:
            unnamed += 1
            return OTHER
        return scope

    out = T.load(trace_dir, label)
    out["unnamed"] = unnamed
    return out


def _self_times(iv: List[Tuple[str, float, float]]) -> Dict[str, float]:
    """Time of each label over nested intervals, each instant going to
    the innermost interval that holds it (ops on one line nest or are
    disjoint)."""
    out: Dict[str, float] = {}
    stack: List[list] = []           # [label, start, end, children's time]

    def close():
        label, a, b, kids = stack.pop()
        out[label] = out.get(label, 0.0) + (b - a) - kids
        if stack:
            stack[-1][3] += b - a

    for label, a, b in sorted(iv, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][2] <= a:
            close()
        stack.append([label, a, b, 0.0])
    while stack:
        close()
    return out


def decode_scopes(kept: dict, span_ns: Tuple[float, float],
                  module: str = DECODE) -> Dict[str, float]:
    """Device seconds of each scope inside ``module`` over ``span_ns``
    (profiler clock), averaged over devices; ``other`` holds the ops no
    scope names."""
    lo, hi = span_ns
    devs = kept["devices"]
    total: Dict[str, float] = {}
    for dev in devs:
        mods = sorted((s, s + d)
                      for name, s, d in dev["lines"].get(T.MODULES_LINE, [])
                      if T.module_name(name) == module
                      and s + d > lo and s < hi)
        if not mods:
            continue
        starts = [a for a, _ in mods]
        iv = []
        for label, s, d in dev["lines"].get(T.OPS_LINE, []):
            # the module run that holds the op's start
            i = bisect.bisect_right(starts, s) - 1
            if i < 0:
                continue
            a = max(s, mods[i][0], lo)
            b = min(s + d, mods[i][1], hi)
            if b > a:
                iv.append((label or OTHER, a, b))
        for label, t in _self_times(iv).items():
            total[label] = total.get(label, 0.0) + t * 1e-9
    n = max(1, len(devs))
    return {k: v / n for k, v in total.items()}


# ---------------------------------------------------------------------------
# program spans
# ---------------------------------------------------------------------------


def gap_labels(spans, origin_ns: int, offset_ns: float
               ) -> List[Tuple[str, float, float]]:
    """``trace.reduce``'s ``host_spans`` from the program's spans (all
    but the per-request ``request`` category), mapped onto the profiler
    clock (``offset_ns`` = profiler ns − monotonic ns, from the
    ``chipbench.mark`` event) and listed innermost first: a child is
    never longer than its parent."""
    out = [(f"{s.name} {s.track}", origin_ns + s.start * 1e9 + offset_ns,
            origin_ns + s.end * 1e9 + offset_ns)
           for s in spans if s.cat != "request"]
    out.sort(key=lambda x: x[2] - x[1])
    return out


def _in_window(spans, window, name, cat="step"):
    w0, w1 = window
    return [s for s in spans if s.name == name and s.cat == cat
            and w0 <= s.start < w1]


def poll_p95_ms(spans, window) -> Optional[float]:
    """95th percentile of the window's ``poll`` span durations, in ms."""
    v = percentile([s.duration for s in _in_window(spans, window, "poll")],
                   95)
    return None if v is None else 1e3 * v


def host_gap_ms(spans, window) -> Optional[float]:
    """Window time in which no engine has device work outstanding, per
    decode step, in ms. Work is outstanding from a ``*.dispatch`` start
    to the end of the ``*.sync`` that follows it on the same engine; a
    ``prefill.merge`` stays outstanding until that engine's next sync
    ends."""
    w0, w1 = window
    by_track: Dict[str, list] = {}
    for s in spans:
        if s.cat == "step" and s.name.split(".")[-1] in (
                "dispatch", "sync", "merge"):
            by_track.setdefault(s.track, []).append(s)
    busy = []
    for track in by_track.values():
        track.sort(key=lambda s: (s.start, s.end))
        opened = None
        for s in track:
            kind = s.name.split(".")[-1]
            if kind == "sync":
                if opened is not None:
                    busy.append((opened, s.end))
                opened = None
            elif opened is None:
                opened = s.start
    covered = sum(b - a for a, b in T._union(T._clip(busy, w0, w1)))
    steps = len(_in_window(spans, window, "decode", "iteration"))
    if not steps:
        return None
    return 1e3 * ((w1 - w0) - covered) / steps
