"""Least time of the decode steps' held experts (the pairs routed to
them, and the weights of those some row picks, under uniform routing)
over the device time of the decode program's ``experts`` scope, in
percent."""
from chipbench.metrics._common import scope_roofline


def read(run):
    return scope_roofline(run, "experts")
