#!/usr/bin/env python3
"""What a held-expert cell's routing does on the chip.

    python3 chipbench/experts.py --workload <cell> --seed <n> \
        --seconds 20 [--trace 1] [--sequences 4 --length 384]

In this one process: (a) one run of the cell, as ``run.py`` makes it,
reading each engine's ``expert_counters()`` after the warm-up and after
the drain: the held experts touched and the pairs routed to them, per
expert layer per decode step, beside what ``scope_cost`` takes for
them (the arch module's ``held_touched`` / ``held_pairs`` at
``max_batch`` rows, uniform routing); (b) the top-k selections that
differ between the program's prefill (its arithmetic, the router at
HIGHEST) and the plain reference, over ``--sequences`` prompts of
``--length`` random tokens, each under an adapter of the cell. One JSON
line each. Needs a TPU, as ``run.py`` does.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import random  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def counters_run(harness, cell, seed: int, seconds: float, trace: bool,
                 t_start=None) -> dict:
    """One run of ``cell``, and its engines' expert counters over the
    window and the drain, per expert layer per decode step."""
    got = {}
    drive = harness.drive

    def counted(cell_, cluster, plans, **kw):
        engines = [e for e in cluster.backend.engines if e is not None]
        got["rows"] = engines[0].max_batch
        got["before"] = [e.expert_counters() for e in engines]
        try:
            return drive(cell_, cluster, plans, **kw)
        finally:
            got["after"] = [e.expert_counters() for e in engines]

    harness.drive = counted
    try:
        res = harness.run_cell(cell, seed, seconds, trace, t_start=t_start)
    finally:
        harness.drive = drive
    steps, touched, pairs = 0, None, None
    for b, a in zip(got["before"], got["after"]):
        steps += a["steps"] - b["steps"]
        t = [x - y for x, y in zip(a["touched"], b["touched"])]
        p = [x - y for x, y in zip(a["pairs"], b["pairs"])]
        touched = t if touched is None else [u + v for u, v in
                                             zip(touched, t)]
        pairs = p if pairs is None else [u + v for u, v in zip(pairs, p)]
    touched = [t / steps for t in touched]
    pairs = [p / steps for p in pairs]
    arch, cfg = cell.arch, cell.config
    return {"seed": seed, "decode_steps": steps,
            "touched_per_layer": touched, "pairs_per_layer": pairs,
            "touched_mean": sum(touched) / len(touched),
            "pairs_mean": sum(pairs) / len(pairs),
            "scope_cost_touched": arch.held_touched(cfg, got["rows"]),
            "scope_cost_pairs": arch.held_pairs(cfg, got["rows"]),
            "correct": res["correct"], "metrics": res["metrics"],
            "checks": res["checks"], "device": res["device"],
            "breakdown": res.get("breakdown")}


def route_flips(cell, seed: int, sequences: int, length: int) -> dict:
    """Top-k selections of the program's prefill that the reference
    does not make, over ``sequences`` random prompts."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench import generator, weights
    from repro.lora.bank import build_bank
    from repro.models import ffn
    from repro.models import model as M

    cfg, arch = cell.config, cell.arch
    mc = arch.model_config(cfg)
    chosen = []
    held_moe_ffn = ffn.held_moe_ffn

    def logged(c, p, x, proj=None):
        # the program's own routing, recomputed by the same ops
        logits = jnp.dot(x.reshape(-1, x.shape[-1]).astype(jnp.float32),
                         p["router"].astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        _, topi = ffn._gate(c.moe, jax.nn.softmax(logits, axis=-1))
        jax.debug.callback(lambda t: chosen.append(np.asarray(t)), topi,
                           ordered=True)
        return held_moe_ffn(c, p, x, proj)

    params = weights.make_params(arch, cfg, seed)
    ranks = generator.adapters_of(cell.mix)
    with weights.served_adapters(arch, cfg, seed):
        bank = build_bank(mc, ranks, None, n_layers=mc.n_layers)
    rng = random.Random(seed)
    out = {"seed": seed, "length": length, "selections": 0,
           "differing": 0, "token_layers_differing": 0}
    ffn.held_moe_ffn = logged
    try:
        for _ in range(sequences):
            aid = rng.choice(sorted(ranks))
            toks = np.asarray([rng.randrange(1, cfg["vocab_size"])
                               for _ in range(length)], np.int32)
            chosen.clear()
            idx = bank.lora_idx(jnp.asarray([bank.index(aid)], jnp.int32))
            logits, _ = M.prefill(mc, params, jnp.asarray(toks[None]),
                                  bank=bank.data, lora_idx=idx,
                                  cache_len=length)
            jax.block_until_ready(logits)
            jax.effects_barrier()
            prog = np.stack([t.reshape(length, -1) for t in chosen])
            ad = weights.make_adapter(arch, cfg, seed, aid, ranks[aid])
            _, ref = arch.routes_and_logits(cfg, params, ad, toks)
            ref = np.asarray(ref)
            for p_row, r_row in zip(prog.reshape(-1, prog.shape[-1]),
                                    ref.reshape(-1, ref.shape[-1])):
                d = len(set(p_row.tolist()) - set(r_row.tolist()))
                out["differing"] += d
                out["token_layers_differing"] += d > 0
            out["selections"] += int(prog.size)
    finally:
        ffn.held_moe_ffn = held_moe_ffn
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--sequences", type=int, default=4)
    ap.add_argument("--length", type=int, default=384)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from chipbench import harness
    from chipbench.run import setup_jax
    cell = harness.load_cell(args.workload, ROOT)
    if not setup_jax(cell.chips):
        return 2
    print(json.dumps(counters_run(harness, cell, args.seed, args.seconds,
                                  bool(args.trace), T_START)), flush=True)
    print(json.dumps(route_flips(cell, args.seed + 1, args.sequences,
                                 args.length)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
