"""95th percentile, over the window's finished requests, of the time
per output token after the first."""
from chipbench.metrics._common import p95


def read(run):
    v = p95(t for t in (r.tpot() for r in run.requests) if t is not None)
    return None if v is None else 1e3 * v
