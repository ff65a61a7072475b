"""Paper Fig 14 — adapter movement through the tiered data plane.

Three sweeps on the ``AdapterStore``/``NetworkModel`` API:

1. fetch latency by source (local host mem, IB GDR, SSD, TPU ICI) x
   transfer size x fabric contention — the paper's headline shape:
   IB GDR ~ local host->GPU, SSD prohibitive;
2. load-aware source quotes: the same IB GDR fetch priced against a
   source link already carrying 0/2/4 in-flight transfers (what
   ``FetchPlan`` source selection routes around);
3. access-mode A/B on a drifting workload (shifting rank popularity,
   dynamic LORASERVE placement): lazy migrate-on-miss vs GDR
   remote-read vs rebalance prefetch vs both — P95 TTFT plus data-plane
   telemetry per mode.
"""
from __future__ import annotations

import copy

from repro.cluster import NetworkModel
from repro.traces import make_adapters, synth_trace

from .common import emit, sim_cluster

RANK_NBYTES = {r: r * 16_000_000 for r in (8, 16, 32, 64, 128)}


def run(fast: bool = True):
    rows = []
    # -- 1. Fig 14: source x size x contention --------------------------
    for contention in (1.0, 4.0):
        net = NetworkModel(contention=contention)
        for mb in (64, 256, 1024, 2048):
            nbytes = mb * 1024 * 1024
            for src in net.sources():
                lat = net.transfer_latency(nbytes, src)
                rows.append(emit(f"fig14/{src}/{mb}MB/x{contention:g}",
                                 lat * 1e6, f"latency_s={lat:.4f}"))
    net = NetworkModel()
    l_ib = net.transfer_latency(2 << 30, "ib_gdr")
    l_host = net.transfer_latency(2 << 30, "local_host")
    l_ssd = net.transfer_latency(2 << 30, "ssd")
    rows.append(emit("fig14/ib_vs_host", 0.0, f"ratio={l_ib / l_host:.2f}"))
    rows.append(emit("fig14/ssd_vs_host", 0.0,
                     f"ratio={l_ssd / l_host:.2f}"))

    # -- 2. link-load-aware quotes (FetchPlan source selection) ----------
    for load in (0, 2, 4):
        net = NetworkModel()
        for _ in range(load):
            net.begin_transfer(1 << 30, "ib_gdr", now=0.0, src_server=0)
        lat = net.plan_latency(256 << 20, "ib_gdr", now=0.0, src_server=0)
        rows.append(emit(f"link_load/ib_gdr/256MB/{load}_inflight",
                         lat * 1e6, f"latency_s={lat:.4f}"))
    pen = NetworkModel().remote_read_penalty(256 << 20)
    rows.append(emit("remote_read/iter_penalty/256MB", pen * 1e6,
                     f"penalty_s={pen:.4f}"))

    # -- 3. access-mode A/B under drift ----------------------------------
    adapters = make_adapters(32 if fast else 48,
                             nbytes_per_rank=RANK_NBYTES, seed=1)
    trace = synth_trace(adapters, rps=12 if fast else 14,
                        duration=60 if fast else 120,
                        popularity="shifting", seed=2)
    modes = [
        ("migrate", {}),
        ("remote-read", {"access_mode": "remote-read"}),
        ("migrate+prefetch", {"prefetch": True}),
        ("remote-read+prefetch", {"access_mode": "remote-read",
                                  "prefetch": True}),
    ]
    for name, kw in modes:
        sim = sim_cluster(4, adapters, timeout=60, policy="loraserve",
                          seed=3, warmup=15, rebalance_period=8.0, **kw)
        res = sim.run(copy.deepcopy(trace))
        rows.append(emit(
            f"access_mode/{name}", res.p95_ttft() * 1e6,
            f"p95_ttft_s={res.p95_ttft():.4f};"
            f"p50_ttft_s={res.p50_ttft():.4f};"
            f"mean_tbt_ms={res.mean_tbt() * 1e3:.2f};"
            f"fetches={res.fetches};remote_reads={res.remote_reads};"
            f"prefetches={res.prefetches};"
            f"coalesced={res.coalesced_fetches};"
            f"timed_out={res.timed_out}"))
    return rows
