"""Mesh-sharded serving benchmark: per-chip work, modeled-vs-simulated
iteration time, and real-engine tokens/s versus tensor-parallel degree.

Three row families per tp degree:

  * ``mesh/modeled_decode_tp{t}`` / ``mesh/modeled_prefill_tp{t}`` —
    the cost model's iteration times with the explicit ICI ring-all-
    reduce terms (`ServerModel(mesh_shape=(1, t))`), plus the per-chip
    weight bytes / FLOPs each degree leaves on one chip (strictly
    decreasing with tp: that is the point of sharding).
  * ``mesh/sim_iter_tp{t}`` — a discrete-event `SimServer` run of a
    ramping trace (staggered output lengths, mixed rank buckets). The
    simulated ICI seconds (mesh run minus an otherwise-identical
    no-mesh run) are compared against the closed-form steady-state ICI
    estimate (constant batch, every decode iteration alike) — the
    relative gap is the reported cost-model ICI error, nonzero because
    the real batch ramps down at the tail.
  * ``mesh/engine_tp{t}`` — the real JAX engine on a (1, t) device
    mesh: wall-clock us/token + tokens/s, and the *measured* per-chip
    parameter bytes of the sharded arrays (addressable shard 0).
    Degrees above the process device count are skipped — run under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the CI
    ``mesh`` job does) to sweep all of them. CPU wall-clock does not
    reward sharding (all "chips" share one socket); the acceptance
    signal is per-chip work, not CPU tokens/s.
"""
from __future__ import annotations

import time

from repro.cluster.costmodel import ServerModel
from repro.cluster.server import SimServer
from repro.core import ServeRequest

from .common import emit

TP_DEGREES = (1, 2, 4, 8)


# -- cost model: iteration times + per-chip work ---------------------------

def modeled_rows(fast: bool):
    rows = []
    batch, rank, tokens = 32, 64, 2048
    for tp in TP_DEGREES:
        m = ServerModel(tp=tp, mesh_shape=(1, tp))
        ici = m.iteration_ici_time(batch, {rank: batch})
        per_chip_gb = 2.0 * m.n_params / tp / 1e9
        rows.append(emit(
            f"mesh/modeled_decode_tp{tp}",
            m.decode_time(batch, rank) * 1e6,
            f"ici_us={ici * 1e6:.1f} "
            f"per_chip_weight_gb={per_chip_gb:.3f}"))
        rows.append(emit(
            f"mesh/modeled_prefill_tp{tp}",
            m.prefill_time(tokens, rank) * 1e6,
            f"per_chip_flops_per_token_g={per_chip_gb:.3f}"))
    return rows


# -- discrete-event sim vs the closed-form ICI estimate --------------------

def _trace(n_req: int):
    # mixed rank buckets, staggered output lengths: the tail iterations
    # run at shrinking batch, which the constant-batch estimate ignores
    return [ServeRequest(req_id=i, adapter_id=f"a{i}",
                         rank=(8, 64)[i % 2], prompt_len=128,
                         output_len=32 + (i % 3) * 16, arrival=0.0)
            for i in range(n_req)]


def _sim_run(model: ServerModel, n_req: int) -> SimServer:
    s = SimServer(0, model, bank_mode="bucketed")
    for r in _trace(n_req):
        s.enqueue(r)
    now = 0.0
    while s.waiting or s.running:
        now = s.step(now)
    return s


def sim_rows(fast: bool):
    rows = []
    n_req = 16 if fast else 32
    reqs = _trace(n_req)
    b = len(reqs)
    buckets = {8: sum(1 for r in reqs if r.rank == 8),
               64: sum(1 for r in reqs if r.rank == 64)}
    n_dec = max(r.output_len for r in reqs) - 1
    tokens = sum(r.prompt_len for r in reqs)
    for tp in TP_DEGREES:
        mesh = _sim_run(ServerModel(tp=tp, mesh_shape=(1, tp)), n_req)
        flat = _sim_run(ServerModel(tp=tp), n_req)
        sim_ici = mesh.busy_time - flat.busy_time
        m = ServerModel(tp=tp, mesh_shape=(1, tp))
        # steady-state closed form: one full-batch prefill, then every
        # decode iteration at the full batch / full bucket mix
        modeled_ici = m.iteration_ici_time(tokens, dict(buckets)) \
            + n_dec * m.iteration_ici_time(b, dict(buckets))
        err = abs(modeled_ici - sim_ici) / sim_ici if sim_ici > 0 \
            else 0.0
        rows.append(emit(
            f"mesh/sim_iter_tp{tp}",
            mesh.busy_time / mesh.iterations * 1e6,
            f"iters={mesh.iterations} "
            f"sim_ici_us={sim_ici * 1e6:.1f} "
            f"modeled_ici_us={modeled_ici * 1e6:.1f} "
            f"ici_err={err:.3f}"))
    return rows


# -- real engine on a (1, tp) device mesh ----------------------------------

def _per_chip_param_mb(params) -> float:
    import jax
    total = 0
    for leaf in jax.tree_util.tree_leaves(params):
        shards = getattr(leaf, "addressable_shards", None)
        total += shards[0].data.nbytes if shards else leaf.nbytes
    return total / 2**20


def engine_rows(fast: bool):
    import jax

    from repro.configs import get_smoke_config
    from repro.launch.mesh import make_engine_mesh
    from repro.models import model as M
    from repro.serving import Request, ServingEngine

    cfg = get_smoke_config("llama-7b-paper")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    ranks = {"a-r8": 8, "b-r64": 64}
    n_new = 8 if fast else 16
    rows = []
    for tp in TP_DEGREES:
        if tp > len(jax.devices()):
            continue            # needs --xla_force_host_platform_device_count
        mesh = make_engine_mesh(1, tp) if tp > 1 else None
        eng = ServingEngine(cfg, params, dict(ranks), max_batch=4,
                            max_len=8 + n_new + 4, bank_mode="bucketed",
                            lora_kernel="einsum", mesh=mesh)

        def run(base):
            for i in range(4):
                eng.submit(Request(base + i, ("a-r8", "b-r64")[i % 2],
                                   list(range(1, 9)), n_new))
            eng.run_until_drained()

        run(0)                  # warm the traces
        t0 = time.perf_counter()
        run(100)
        dt = time.perf_counter() - t0
        toks = 4 * n_new
        rows.append(emit(
            f"mesh/engine_tp{tp}", dt / toks * 1e6,
            f"tokens_per_s={toks / dt:.1f} "
            f"per_chip_param_mb={_per_chip_param_mb(eng.params):.2f}"))
    return rows


def run(fast: bool = True):
    return modeled_rows(fast) + sim_rows(fast) + engine_rows(fast)
