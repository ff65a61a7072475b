"""Reductions shared by several metric readers. Each reader file,
``<metric name>.py``, defines ``read(run)`` and returns a number, or
None where the run holds nothing to read."""
from __future__ import annotations

from chipbench.harness import percentile


def decode_step_ms(run):
    """The window's decode iteration spans (engine clock, after the
    device sync), total over count."""
    s = run.decode_spans
    return 1e3 * sum(s) / len(s) if s else None


def decode_roofline(run):
    """Least time of the traced decode steps over the device time of the
    decode program (``jit__decode``) in the same span, in percent."""
    p = run.profile
    if not p or not p.get("decode_device_s") or not p["decode_steps"]:
        return None
    return 100.0 * p["decode_least_s"] / p["decode_device_s"]


def mfu(run):
    """Model operations of every prefill and decode token of the traced
    span over the span times the chip's peak, in percent."""
    p = run.profile
    if not p or not p["model_flops"]:
        return None
    return 100.0 * p["model_flops"] / (p["span_s"] * p["peak_flops"])


def scope_ms(run, scope):
    """Device time of ``scope`` in the decode program
    (``scope_device_s``) per traced decode step, in ms."""
    p = run.profile
    if not p or not p["decode_steps"] or not p["scope_device_s"].get(scope):
        return None
    return 1e3 * p["scope_device_s"][scope] / p["decode_steps"]


def scope_roofline(run, scope):
    """Least time of ``scope`` over the traced decode steps
    (``scope_least_s``, the architecture's ``scope_cost``) over its
    device time in the decode program, in percent."""
    p = run.profile
    if not p or not p["scope_device_s"].get(scope) \
            or not p["scope_least_s"].get(scope):
        return None
    return 100.0 * p["scope_least_s"][scope] / p["scope_device_s"][scope]


def device_idle(run):
    """Share of the traced span in which no operation ran on the device,
    in percent."""
    p = run.profile
    if not p or not p["window_s"]:
        return None
    return 100.0 * (1.0 - p["busy_s"] / p["window_s"])


def p95(values):
    return percentile(list(values), 95)
