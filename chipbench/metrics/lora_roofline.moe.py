"""Least time of the decode steps' LoRA deltas over the device time of
the decode program's ``lora`` scope, in percent."""
from chipbench.metrics._common import scope_roofline


def read(run):
    return scope_roofline(run, "lora")
