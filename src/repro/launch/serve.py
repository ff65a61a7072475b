"""Serving launcher: a miniature LORASERVE cluster of real JAX engines
driven through the unified ``LoRAServeCluster`` facade.

Each "server" is a placement-aware ``ServingEngine`` over the same
(reduced) base model whose LoRA bank holds *only its placed adapter
subset* (a server hosting ranks {8, 16} pays a 16-wide bank, not the
global max). The facade owns the paper's control plane — placement +
phi-routing + distributed pool + demand estimation — and applies
``end_of_timestep`` rebalances while requests are in flight: arrivals
are spread over wall-clock time with drifting adapter popularity, so at
least one mid-run rebalance re-places adapters and re-seeds routing
before the trace drains.

The engines run the real model on whatever device JAX finds:
``--size smoke`` (the default) builds the 2-layer reduced config that
the CPU tests use, ``--size full`` the registered config at its
published widths, for the accelerator (``chip_smoke.py`` drives this
path on one TPU chip). Compiled programs are kept in JAX's persistent
cache (``launch.compile_cache``).

Example:
  PYTHONPATH=src python -m repro.launch.serve --arch llama-7b-paper \
      --servers 2 --adapters 8 --requests 24 --policy loraserve
"""
from __future__ import annotations

import argparse
import random

import jax

from repro.cluster import NetworkModel
from repro.configs import get_config, get_smoke_config
from repro.core import AdapterInfo, POLICIES, ServeRequest
from repro.models import model as M
from repro.serving import EngineBackend, LoRAServeCluster

from .compile_cache import enable_compile_cache


def build_trace(adapters, cfg, n_requests: int, prompt_len: int,
                max_new: int, duration: float, seed: int):
    """Arrivals spread over `duration` seconds with drifting popularity:
    early traffic favors low-rank adapters, late traffic high-rank —
    the workload shift that makes the dynamic policy re-place."""
    rng = random.Random(seed)
    by_rank = sorted(adapters, key=lambda a: a.rank)
    trace = []
    for i in range(n_requests):
        progress = i / max(1, n_requests - 1)
        # weight drifts from head (low ranks) to tail (high ranks)
        w = [(1.0 - progress) * (len(by_rank) - j) + progress * (j + 1)
             for j in range(len(by_rank))]
        a = rng.choices(by_rank, weights=w)[0]
        prompt = [rng.randrange(1, cfg.vocab_size)
                  for _ in range(prompt_len)]
        trace.append(ServeRequest(
            req_id=i, adapter_id=a.adapter_id, rank=a.rank,
            prompt_len=prompt_len, output_len=max_new, prompt=prompt,
            arrival=i * duration / max(1, n_requests)))
    return trace


def build_model(arch: str, size: str, seed: int):
    """``(cfg, params)`` for ``arch``: the reduced smoke config or the
    full published one, with fp32 weights drawn from ``seed``."""
    cfg = get_config(arch) if size == "full" else get_smoke_config(arch)
    params = jax.jit(M.init_params, static_argnums=0)(
        cfg, jax.random.PRNGKey(seed))
    return cfg, params


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama-7b-paper")
    ap.add_argument("--size", default="smoke", choices=["smoke", "full"],
                    help="model size: the 2-layer reduced config (smoke) "
                         "or the published widths and depth (full)")
    ap.add_argument("--servers", type=int, default=2)
    ap.add_argument("--adapters", type=int, default=8)
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--policy", default="loraserve",
                    choices=sorted(POLICIES))
    ap.add_argument("--bank-mode", default="padded",
                    choices=["padded", "bucketed"],
                    help="LoRA bank layout: max-rank padded (paper "
                         "baseline) or power-of-two rank buckets")
    ap.add_argument("--decode-block", type=int, default=1,
                    help="decode tokens per fused host dispatch "
                         "(ServingEngine.decode_steps(k); 1 = one "
                         "round-trip per token)")
    ap.add_argument("--lora-kernel", default="einsum",
                    choices=["einsum", "sgmv"],
                    help="LoRA delta execution form: gather-einsum "
                         "(any backend) or the fused Pallas SGMV "
                         "kernels (compiled on TPU, interpreted "
                         "elsewhere)")
    ap.add_argument("--mesh", default=None, metavar="DP,TP",
                    help="mesh-sharded engines: 'dp,tp' shards each "
                         "engine over a (data, model) device mesh with "
                         "co-sharded LoRA banks (needs dp*tp devices; "
                         "on CPU set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N)")
    ap.add_argument("--access-mode", default="migrate",
                    choices=["migrate", "remote-read"],
                    help="on a placement miss: block on the adapter "
                         "fetch (migrate) or serve immediately reading "
                         "weights from a peer's copy over GDR while the "
                         "local copy warms (remote-read)")
    ap.add_argument("--prefetch", action="store_true",
                    help="warm newly-placed adapters at each rebalance "
                         "instead of migrating lazily on first hit")
    ap.add_argument("--controller", action="store_true",
                    help="run the SLO-driven control plane: drift "
                         "detection, triggered rebalances, and server "
                         "scale-up/drain between --min-servers and "
                         "--max-servers")
    ap.add_argument("--slo-ttft", type=float, default=5.0,
                    help="TTFT target (seconds) the controller defends")
    ap.add_argument("--slo-target", type=float, default=0.95,
                    help="required fraction of requests inside the SLO")
    ap.add_argument("--min-servers", type=int, default=1)
    ap.add_argument("--max-servers", type=int, default=4)
    ap.add_argument("--tick-period", type=float, default=1.0,
                    help="controller tick (seconds)")
    ap.add_argument("--serve", default=None, metavar="HOST:PORT",
                    help="instead of replaying a trace, serve the "
                         "cluster over the streaming HTTP gateway "
                         "(OpenAI-style /v1/completions with SSE, "
                         "adapter lifecycle routes, /metrics) until "
                         "SIGTERM; port 0 picks an ephemeral port")
    ap.add_argument("--rate", type=float, default=None,
                    help="gateway: per-tenant admission rate (req/s)")
    ap.add_argument("--max-inflight", type=int, default=None,
                    help="gateway: per-tenant concurrent-request cap")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write the run's span trace on exit: Perfetto/"
                         "Chrome-trace JSON (open in ui.perfetto.dev), "
                         "or one-span-per-line JSONL when PATH ends in "
                         ".jsonl")
    ap.add_argument("--flight-recorder", default=None, metavar="DIR",
                    help="keep a bounded ring of recent spans and dump "
                         "it (plus a controller-decision audit record) "
                         "into DIR on SLO violations, scale-up/drain "
                         "decisions, and timeouts")
    ap.add_argument("--chaos", type=int, default=None, metavar="SEED",
                    help="chaos plane: inject a seeded random fault "
                         "storm (server crashes/restores, link flaps, "
                         "fetch stalls) over the run; crashes are "
                         "detected by heartbeat and recovered "
                         "loss-free")
    ap.add_argument("--fault-plan", default=None, metavar="PATH",
                    help="chaos plane: replay an explicit JSON fault "
                         "schedule (see repro.faults.FaultPlan) "
                         "instead of a random storm")
    ap.add_argument("--detector-window", type=float, default=0.5,
                    help="heartbeat silence (seconds) before a server "
                         "is confirmed dead and recovery runs")
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--duration", type=float, default=6.0,
                    help="seconds the trace arrivals span")
    ap.add_argument("--rebalance-period", type=float, default=1.5)
    ap.add_argument("--timeout", type=float, default=120.0,
                    help="seconds a request may wait in a server queue "
                         "before it is dropped as timed out")
    ap.add_argument("--seed", type=int, default=0)
    return ap


def main(argv=None, *, model=None):
    """Run the launcher and return its ``ClusterReport``. ``model`` is
    an already built ``(cfg, params)`` from ``build_model`` for the same
    ``--arch``/``--size``/``--seed``, so that a caller running several
    replays initialises the weights once."""
    args = build_parser().parse_args(argv)
    enable_compile_cache()
    cfg, params = model or build_model(args.arch, args.size, args.seed)

    ranks = [8, 16, 32, 64, 128]
    adapters = [AdapterInfo(f"ad{i}-r{ranks[i % 5]}", ranks[i % 5],
                            nbytes=ranks[i % 5] * 2_000_000)
                for i in range(args.adapters)]

    controller = None
    if args.controller:
        from repro.controlplane import (ClusterController,
                                        ControllerConfig, SLOSpec)
        controller = ClusterController(
            SLOSpec(ttft=args.slo_ttft, target=args.slo_target,
                    window=max(4 * args.tick_period, 2.0)),
            ControllerConfig(tick_period=args.tick_period,
                             min_servers=args.min_servers,
                             max_servers=args.max_servers))

    mesh_shape = None
    if args.mesh:
        dp, tp = (int(v) for v in args.mesh.split(","))
        mesh_shape = (dp, tp)
    backend = EngineBackend(cfg, params, args.servers, max_batch=4,
                            max_len=args.prompt_len + args.max_new + 8,
                            seed=args.seed, timeout=args.timeout,
                            bank_mode=args.bank_mode,
                            decode_block=args.decode_block,
                            lora_kernel=args.lora_kernel,
                            mesh_shape=mesh_shape)
    tracer = recorder = None
    if args.trace_out or args.flight_recorder:
        from repro.obs import FlightRecorder, Tracer, WallClock
        tracer = Tracer(clock=WallClock())
        if args.flight_recorder:
            recorder = FlightRecorder(out_dir=args.flight_recorder)
    fault_plan = None
    if args.fault_plan:
        from repro.faults import FaultPlan
        fault_plan = FaultPlan.load(args.fault_plan)
    elif args.chaos is not None:
        from repro.faults import FaultPlan
        fault_plan = FaultPlan.random_plan(
            args.chaos, horizon=args.duration, n_servers=args.servers)
    cluster = LoRAServeCluster(
        backend, adapters, policy=args.policy, network=NetworkModel(),
        rebalance_period=args.rebalance_period, seed=args.seed,
        access_mode=args.access_mode, prefetch=args.prefetch,
        controller=controller, tracer=tracer, flight_recorder=recorder,
        fault_plan=fault_plan, detector_window=args.detector_window,
        durable_ssd=fault_plan is not None)

    def _write_trace():
        if tracer is None or not args.trace_out:
            return
        from repro.obs import write_jsonl, write_perfetto
        writer = (write_jsonl if args.trace_out.endswith(".jsonl")
                  else write_perfetto)
        n = writer(tracer, args.trace_out)
        print(f"trace: {n} spans -> {args.trace_out}")

    if args.serve:
        from .server import run_gateway
        host, _, port = args.serve.rpartition(":")
        report = run_gateway(cluster, host or "127.0.0.1", int(port),
                             rate=args.rate,
                             max_inflight=args.max_inflight)
        print(f"served={report.completed()} "
              f"timed_out={report.timed_out} "
              f"registered={report.registered} "
              f"unregistered={report.unregistered}")
        _write_trace()
        print("gateway drained OK")
        return report

    trace = build_trace(adapters, cfg, args.requests, args.prompt_len,
                        args.max_new, args.duration, args.seed)
    report = cluster.run(trace)

    for sid, mem in enumerate(report.memory_profile):
        print(f"server {sid}: requests={report.per_server_counts[sid]} "
              f"bank_adapters={mem['n_adapters']} "
              f"bank_max_rank={mem['max_rank']}")
    s = report.summary
    print(f"bank_mode={report.bank_mode} mesh={report.mesh_shape}")
    print(f"policy={args.policy} finished={report.completed()}"
          f"/{len(trace)} timed_out={report.timed_out} "
          f"p95_ttft={s['p95_ttft']:.3f}s "
          f"mean_tbt={s['mean_tbt'] * 1e3:.1f}ms "
          f"fetch_latency(mean)={s['mean_fetch_latency'] * 1e3:.1f}ms")
    print(f"rebalances={report.rebalances} "
          f"placement_changed={report.placement_changed()} "
          f"pool_fetches={report.fetches} "
          f"max_adapters/server={report.max_adapters_per_server}")
    print(f"access_mode={report.access_mode} "
          f"remote_reads={report.remote_reads} "
          f"prefetches={report.prefetches} "
          f"coalesced_fetches={report.coalesced_fetches}")
    if fault_plan is not None:
        print(f"chaos: failures={report.server_failures} "
              f"recoveries={report.recoveries} "
              f"redispatched={report.redispatched} "
              f"fetch_retries={report.fetch_retries} "
              f"fetch_timeouts={report.fetch_timeouts} "
              f"breaker_opens={report.breaker_opens}")
    if args.controller:
        print(f"controller: slo_attainment={report.slo_attainment(args.slo_ttft):.3f} "
              f"scale_ups={report.scale_ups} drains={report.drains} "
              f"retires={report.retires} "
              f"oob_rebalances={report.controller_rebalances} "
              f"final_servers={report.final_servers} "
              f"gpu_seconds={report.gpu_seconds:.1f} "
              f"drift_events={len(report.drift_events)}")
    if tracer is not None:
        _write_trace()
        for phase, d in sorted(report.cost_drift.items()):
            print(f"costmodel[{phase}]: n={d['count']} "
                  f"modeled={d['modeled_s']:.3f}s "
                  f"measured={d['measured_s']:.3f}s "
                  f"bias={d['bias']:+.1%} "
                  f"mare={d['mean_abs_rel_err']:.1%}")
        if recorder is not None:
            print(f"flight_recorder: dumps={recorder.n_dumps} "
                  f"-> {args.flight_recorder}")
    print("cluster drained OK")
    return report


if __name__ == "__main__":
    main()
