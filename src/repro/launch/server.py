"""Gateway launcher: run the streaming HTTP serving surface.

  PYTHONPATH=src python -m repro.launch.server --backend sim \
      --servers 2 --adapters 8 --port 8080

Builds a ``LoRAServeCluster`` over either substrate (``--backend sim``
for the discrete-event cost model driven on the wall clock, ``engine``
for real JAX execution), wraps it in ``ServeGateway``, and serves until
SIGTERM/SIGINT — which triggers the graceful drain (stop admitting,
finish in-flight, retire servers) before printing the final report.

``launch/serve.py --serve HOST:PORT`` delegates here with its
engine-backend configuration, so every replay flag (bank mode, kernels,
mesh, access mode, controller) also applies to live serving.
"""
from __future__ import annotations

import argparse
import asyncio
from typing import Optional

from repro.cluster import NetworkModel
from repro.core import AdapterInfo, POLICIES
from repro.serving import LoRAServeCluster, SimBackend


def default_adapters(n: int):
    ranks = [8, 16, 32, 64, 128]
    return [AdapterInfo(f"ad{i}-r{ranks[i % 5]}", ranks[i % 5],
                        nbytes=ranks[i % 5] * 2_000_000)
            for i in range(n)]


def build_sim_cluster(args) -> LoRAServeCluster:
    adapters = default_adapters(args.adapters)
    backend = SimBackend(
        args.servers,
        adapter_nbytes={a.adapter_id: a.nbytes for a in adapters})
    return LoRAServeCluster(
        backend, adapters, policy=args.policy,
        network=NetworkModel(args.servers),
        rebalance_period=args.rebalance_period, seed=args.seed)


def build_engine_cluster(args) -> LoRAServeCluster:
    from repro.serving import EngineBackend

    from .compile_cache import enable_compile_cache
    from .serve import build_model

    enable_compile_cache()
    cfg, params = build_model(args.arch, args.size, args.seed)
    adapters = default_adapters(args.adapters)
    backend = EngineBackend(cfg, params, args.servers, max_batch=4,
                            max_len=args.max_len, seed=args.seed)
    return LoRAServeCluster(
        backend, adapters, policy=args.policy, network=NetworkModel(),
        rebalance_period=args.rebalance_period, seed=args.seed)


def run_gateway(cluster: LoRAServeCluster, host: str, port: int, *,
                rate: Optional[float] = None,
                burst: Optional[float] = None,
                max_inflight: Optional[int] = None,
                announce=print):
    """Serve ``cluster`` on ``host:port`` until a shutdown signal lands,
    then drain gracefully and return the final ``ClusterReport``."""
    from repro.server import AdmissionController, ServeGateway

    admission = AdmissionController(rate=rate, burst=burst,
                                    max_inflight=max_inflight)
    gw = ServeGateway(cluster, host, port, admission=admission)

    async def amain():
        await gw.start()
        announce(f"listening on {gw.host}:{gw.port}")
        await gw.serve_until_stopped()

    asyncio.run(amain())
    return gw.final_report


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8080,
                    help="0 picks an ephemeral port (printed at startup)")
    ap.add_argument("--backend", default="sim",
                    choices=["sim", "engine"],
                    help="execution substrate: calibrated discrete-event "
                         "cost model (sim) or real JAX engines (engine)")
    ap.add_argument("--arch", default="llama-7b-paper",
                    help="base model (engine backend)")
    ap.add_argument("--size", default="smoke", choices=["smoke", "full"],
                    help="engine backend model size: the 2-layer reduced "
                         "config (smoke) or the published widths (full)")
    ap.add_argument("--servers", type=int, default=2)
    ap.add_argument("--adapters", type=int, default=8)
    ap.add_argument("--policy", default="loraserve",
                    choices=sorted(POLICIES))
    ap.add_argument("--rebalance-period", type=float, default=5.0)
    ap.add_argument("--max-len", type=int, default=64,
                    help="engine sequence budget (prompt + output)")
    ap.add_argument("--rate", type=float, default=None,
                    help="per-tenant admission rate (requests/s); "
                         "unset = unlimited")
    ap.add_argument("--burst", type=float, default=None,
                    help="per-tenant token-bucket burst (default: rate)")
    ap.add_argument("--max-inflight", type=int, default=None,
                    help="per-tenant concurrent-request cap")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cluster = (build_sim_cluster(args) if args.backend == "sim"
               else build_engine_cluster(args))
    report = run_gateway(cluster, args.host, args.port, rate=args.rate,
                         burst=args.burst,
                         max_inflight=args.max_inflight)
    s = report.summary
    print(f"served={report.completed()} timed_out={report.timed_out} "
          f"registered={report.registered} "
          f"unregistered={report.unregistered} "
          f"rebalances={report.rebalances}")
    if report.completed():
        print(f"p50_ttft={report.p50_ttft():.3f}s "
              f"p95_ttft={report.p95_ttft():.3f}s "
              f"mean_tbt={(s['mean_tbt'] or 0) * 1e3:.1f}ms")
    print("gateway drained OK")


if __name__ == "__main__":
    main()
