"""Device ms of the decode program's ``attention`` scope per traced
decode step."""
from chipbench.metrics._common import scope_ms


def read(run):
    return scope_ms(run, "attention")
