"""95th percentile of how late the poll loop submitted each window
request after its scheduled time."""
from chipbench.metrics._common import p95


def read(run):
    v = p95(r.t_submit - r.t_sched for r in run.requests
            if r.t_submit != float("inf"))
    return None if v is None else 1e3 * v
