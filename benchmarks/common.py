"""Benchmark plumbing: every bench yields (name, us_per_call, derived)
rows; run.py prints them as CSV."""
from __future__ import annotations

import time


def emit(name: str, us_per_call: float, derived: str):
    return (name, us_per_call, derived)


def fmt_rows(rows):
    out = ["name,us_per_call,derived"]
    for name, us, derived in rows:
        out.append(f"{name},{us:.2f},{derived}")
    return "\n".join(out)


def sim_cluster(n_servers: int, adapters, *, timeout: float = 120.0,
                bank_mode: str = "padded", **cluster_kw):
    """``LoRAServeCluster`` over ``SimBackend(n_servers)``, with adapter
    fetches charged by the modeled fabric (``NetworkModel``)."""
    from repro.cluster import NetworkModel
    from repro.serving import LoRAServeCluster, SimBackend
    backend = SimBackend(n_servers, timeout=timeout, bank_mode=bank_mode,
                         adapter_nbytes={a.adapter_id: a.nbytes
                                         for a in adapters})
    cluster_kw.setdefault("network", NetworkModel())
    return LoRAServeCluster(backend, adapters, **cluster_kw)


def timed(fn, *args, repeat: int = 3, **kw):
    """Returns (result, us_per_call) — best of `repeat`."""
    best = float("inf")
    res = None
    for _ in range(repeat):
        t0 = time.perf_counter()
        res = fn(*args, **kw)
        best = min(best, time.perf_counter() - t0)
    return res, best * 1e6
