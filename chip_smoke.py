#!/usr/bin/env python3
"""Bring-up check: the LoRAServe serving path on a TPU at full width.

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --chips 4   # the mesh-sharded engine, 4 chips

Everything runs in this one process: a chip belongs to one process at a
time. The model is InternLM2-1.8B at its published widths and depth (24
layers, d_model 2048, 16 heads over 8 KV heads, d_ff 8192, vocab 92544)
with fp32 weights drawn from ``--seed``.

With no option:
  (a) fail unless JAX found a TPU and the Pallas kernels compile for it;
  (b) replay a short trace through ``repro.launch.serve.main``: two
      servers, eight adapters of ranks 8..128, the loraserve policy.
      Every request must finish, none may time out, and the control
      loop must rebalance at least once;
  (c) replay it with the Pallas SGMV kernels over padded and over
      bucketed banks (each must finish every request), and check each
      compiled kernel's LoRA delta against ``repro.kernels.ref`` at the
      model's q/o and k/v widths;
  (d) print, per phase, compile counts and seconds, the device's peak
      memory and the request counts.
With ``--chips 4``: the mesh-sharded engine (``--mesh 1,4``) against the
single-device engine on the same prompts (prefill logits at HIGHEST
matmul precision within a stated tolerance, for the einsum and both
SGMV LoRA paths), then a replay on the sharded engines that must finish
every request.

The last line is ``{"ok": true, "device": {...}}``. A failed phase exits
non-zero before it is printed.
"""
from __future__ import annotations

import argparse
import contextlib
import functools
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
ARCH, SIZE = "internlm2-1.8b", "full"
N_REQUESTS, PROMPT_LEN, MAX_NEW = 8, 128, 16
SERVERS, ADAPTERS = 2, 8
# A cold compile of the full-width prefill and decode programs (again
# after a rebalance reshapes a server's bank) stalls the replay loop for
# seconds. No request may be dropped for that in this check, so a queued
# request may wait as long as the whole run may last; there is no
# warm-up replay.
QUEUE_TIMEOUT = 1200.0
# The kernels' LoRA delta vs the fp32 reference, as a share of the
# reference's largest magnitude. On the chip an fp32 matmul may run as
# one bf16 pass: each product then carries a relative error of about
# 2**-8 and the kernel chains two matmuls, so the expected error is a
# few tenths of a percent of the output scale. A wrong adapter, bucket,
# block or output column gives an error of order one.
KERNEL_TOL = 1e-2
# tp=4 vs single-device prefill logits, as a share of the largest
# single-device logit, both computed with fp32 matmuls at HIGHEST
# precision: sharding then only reorders the fp32 sums of the
# contractions it splits (and of the rank-r LoRA psum), which moves the
# logits by about 1e-6 per layer. A misplaced shard, head or missing
# reduction moves them by order one. (At default precision each run
# carries its own bf16 rounding noise, and 24 layers decorrelate it: the
# two differed by 7.0e-3 on v5e, so that comparison cannot tell a
# sharding fault from rounding.)
MESH_TOL = 1e-3


class PhaseFailed(Exception):
    pass


def require(ok: bool, what: str) -> None:
    if not ok:
        raise PhaseFailed(what)


class CompileCounter:
    """Counts XLA compilations (and persistent-cache hits) through
    ``jax.monitoring``; ``take()`` returns what happened since the last
    call."""

    def __init__(self):
        import jax.monitoring
        self._event = "/jax/core/compile/backend_compile_duration"
        self.n = self.hits = 0
        self.secs = 0.0
        self._last = (0, 0.0, 0)
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_dur(self, event, duration_secs, **kw):
        if event == self._event:
            self.n += 1
            self.secs += duration_secs

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def take(self):
        now = (self.n, self.secs, self.hits)
        d = tuple(a - b for a, b in zip(now, self._last))
        self._last = now
        return d


def report_phase(name: str, counter: CompileCounter, t0: float) -> None:
    import jax
    n, secs, hits = counter.take()
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    print(f"[{name}] wall={time.monotonic() - t0:.1f}s compiles={n} "
          f"compile_s={secs:.1f} cache_hits={hits} "
          f"peak_bytes_in_use={peak} (device 0, since start)", flush=True)


def serve_argv(seed: int, *extra: str) -> list:
    return ["--size", SIZE, "--arch", ARCH, "--servers", str(SERVERS),
            "--adapters", str(ADAPTERS), "--requests", str(N_REQUESTS),
            "--prompt-len", str(PROMPT_LEN), "--max-new", str(MAX_NEW),
            "--duration", "2", "--policy", "loraserve",
            "--timeout", str(QUEUE_TIMEOUT), "--seed", str(seed), *extra]


def replay(name, argv, model, counter, *, need_rebalance=False):
    from repro.launch import serve
    t0 = time.monotonic()
    print(f"[{name}] serve {' '.join(argv)}", flush=True)
    report = serve.main(argv, model=model)
    print(f"[{name}] requests: submitted={len(report.results)} "
          f"finished={report.completed()} timed_out={report.timed_out} "
          f"rebalances={report.rebalances} "
          f"placement_changed={report.placement_changed()}", flush=True)
    report_phase(name, counter, t0)
    require(len(report.results) == N_REQUESTS
            and report.completed() == N_REQUESTS,
            f"{name}: {report.completed()}/{N_REQUESTS} requests finished")
    require(report.timed_out == 0, f"{name}: {report.timed_out} timed out")
    require(all(len(r.tokens) == MAX_NEW for r in report.results),
            f"{name}: a request returned the wrong number of tokens")
    if need_rebalance:
        require(report.rebalances >= 1, f"{name}: no rebalance ran")
    return {r.req_id: r.tokens for r in report.results}


def token_agreement(a: dict, b: dict) -> float:
    same = total = 0
    for rid, toks in a.items():
        total += len(toks)
        same += sum(x == y for x, y in zip(toks, b.get(rid, ())))
    return same / max(1, total)


def random_adapters(key, cfg, ranks, n_layers=None):
    """Per-adapter LoRA weights with nonzero B (a fresh adapter's B is
    zero, which would make every delta trivially agree):
    ``{target: {"A": (L, d_in, r), "B": (L, r, d_out)}}``."""
    import jax
    from repro.lora.adapter import init_adapter
    out = []
    for i, r in enumerate(ranks):
        k_a, k_b = jax.random.split(jax.random.fold_in(key, i))
        w = init_adapter(cfg, r, k_a, n_layers=n_layers)
        for t, tw in sorted(w.items()):
            k_b, k = jax.random.split(k_b)
            tw["B"] = jax.random.normal(k, tw["B"].shape) / r ** 0.5
        out.append(w)
    return out


def check_kernels(cfg, seed: int) -> None:
    """The engine's SGMV LoRA callback, compiled for this device, against
    ``kernels.ref.sgmv_ref`` at HIGHEST precision: padded and bucketed
    banks, prefill-shaped (4 x PROMPT_LEN) and decode-shaped (4 x 1)
    activations, at the model's q/o and k/v projection widths."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.ref import sgmv_ref
    from repro.lora.adapter import pad_rank
    from repro.lora.bank import rank_bucket
    from repro.lora.batched import make_lora_cb

    def stack(trees):
        return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)

    ranks = (8, 16, 32, 64, 128, 8)
    rows = np.array([0, 4, 2, 5], np.int32)      # adapter of each row
    key = jax.random.PRNGKey(seed + 1)
    # one layer's slice of each adapter: {target: {"A": (d, r), ...}}
    ads = [jax.tree.map(lambda t: t[0], a)
           for a in random_adapters(key, cfg, ranks, n_layers=1)]
    padded = stack([pad_rank(a, max(ranks)) for a in ads])
    buckets = sorted({rank_bucket(r) for r in ranks})
    members = {b: [i for i, r in enumerate(ranks) if rank_bucket(r) == b]
               for b in buckets}
    bucketed = tuple(stack([pad_rank(ads[i], b) for i in members[b]])
                     for b in buckets)
    bucket_of = {i: (bi, members[b].index(i))
                 for bi, b in enumerate(buckets) for i in members[b]}
    idx_b = jnp.asarray([bucket_of[int(a)] for a in rows], jnp.int32)
    idx_p = jnp.asarray(rows)
    widths = {"q": "q/o", "k": "k/v"}
    worst = 0.0
    for S in (PROMPT_LEN, 1):
        x = jax.random.normal(jax.random.fold_in(key, S),
                              (len(rows), S, cfg.d_model))
        tok = jnp.repeat(idx_p, S)
        for mode, bank, idx in (("padded", padded, idx_p),
                                ("bucketed", bucketed, idx_b)):
            cb = jax.jit(lambda x, bank, idx, name: make_lora_cb(
                bank, idx, kernel="sgmv")(name, x), static_argnums=3)
            for name, label in widths.items():
                y = np.asarray(cb(x, bank, idx, name))
                with jax.default_matmul_precision("highest"):
                    ref = sgmv_ref(x.reshape(-1, cfg.d_model),
                                   padded[name]["A"], padded[name]["B"],
                                   tok)
                ref = np.asarray(ref).reshape(y.shape)
                err = float(np.abs(y - ref).max() / np.abs(ref).max())
                worst = max(worst, err)
                print(f"[kernels] {mode} {label} tokens={x.shape[0] * S} "
                      f"d_out={y.shape[-1]} max|err|/max|ref|={err:.3e} "
                      f"(tol {KERNEL_TOL:g})", flush=True)
                require(np.isfinite(y).all() and err <= KERNEL_TOL,
                        f"kernels: {mode} {label} S={S} error {err:.3e}")
    print(f"[kernels] all within tolerance, worst {worst:.3e}", flush=True)


def run_one_chip(seed: int, counter: CompileCounter) -> None:
    from repro.launch import serve
    t0 = time.monotonic()
    model = serve.build_model(ARCH, SIZE, seed)
    cfg = model[0]
    print(f"[model] {cfg.name}: layers={cfg.n_layers} d_model={cfg.d_model}"
          f" heads={cfg.n_heads}/{cfg.n_kv_heads} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab_size} fp32 seed={seed}", flush=True)
    report_phase("model", counter, t0)
    print(f"[timeout] queue timeout {QUEUE_TIMEOUT:.0f}s and no warm-up: "
          f"cold compiles stall the replay loop and must not drop a "
          f"queued request", flush=True)
    base = replay("einsum", serve_argv(seed), model, counter,
                  need_rebalance=True)
    for mode in ("padded", "bucketed"):
        toks = replay(f"sgmv-{mode}",
                      serve_argv(seed, "--lora-kernel", "sgmv",
                                 "--bank-mode", mode),
                      model, counter)
        print(f"[sgmv-{mode}] token agreement with einsum: "
              f"{token_agreement(base, toks):.3f} (information only)",
              flush=True)
    t0 = time.monotonic()
    check_kernels(cfg, seed)
    report_phase("kernels", counter, t0)


def prefill_logits(eng, toks, aidx):
    """Last-position prefill logits of ``eng`` (its weights, bank and
    sharding) for rows ``toks`` on adapters ``aidx``, with every matmul
    at HIGHEST precision."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import model as M
    fn = jax.jit(functools.partial(
        M.prefill, eng.cfg, cache_len=eng.max_len,
        cache_dtype=jnp.float32, lora_kernel=eng.lora_kernel))
    ctx = eng.sharding.ctx() if eng.sharding is not None \
        else contextlib.nullcontext()
    with ctx, jax.default_matmul_precision("highest"):
        logits, _ = fn(eng.params, toks, bank=eng.bank,
                       lora_idx=eng.lora_bank.lora_idx(aidx))
    return np.asarray(logits)


def run_mesh(seed: int, counter: CompileCounter) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.launch import serve
    from repro.launch.mesh import make_engine_mesh
    from repro.serving import ServingEngine
    from repro.serving.sharding import make_engine_sharding

    t0 = time.monotonic()
    cfg, params = serve.build_model(ARCH, SIZE, seed)
    mesh = make_engine_mesh(1, 4)
    sharded = make_engine_sharding(mesh, cfg, 4).shard_params(params)
    report_phase("model", counter, t0)
    ranks = {"ad0-r8": 8, "ad1-r16": 16, "ad3-r64": 64, "ad4-r128": 128}
    weights = random_adapters(jax.random.PRNGKey(seed + 1), cfg,
                              list(ranks.values()))
    toks = jax.random.randint(jax.random.PRNGKey(seed + 2),
                              (4, PROMPT_LEN), 1, cfg.vocab_size)
    aidx = jnp.arange(4, dtype=jnp.int32)
    for kernel, mode in (("einsum", "padded"), ("sgmv", "padded"),
                         ("sgmv", "bucketed")):
        t0 = time.monotonic()
        out = []
        for mesh_, p in ((None, params), (mesh, sharded)):
            eng = ServingEngine(cfg, p, dict(ranks), max_batch=4,
                                max_len=PROMPT_LEN + MAX_NEW + 8,
                                seed=seed, bank_mode=mode,
                                lora_kernel=kernel, mesh=mesh_)
            for (aid, r), w in zip(ranks.items(), weights):
                eng.install_adapter(aid, r, w)
            out.append(prefill_logits(eng, toks, aidx))
            del eng
        ref, got = out
        err = float(np.abs(got - ref).max() / np.abs(ref).max())
        agree = float((got.argmax(-1) == ref.argmax(-1)).mean())
        print(f"[mesh 1x4 {kernel}-{mode}] prefill logits (HIGHEST) "
              f"max|tp4 - single|/max|single|={err:.3e} "
              f"(tol {MESH_TOL:g}) argmax agreement={agree:.2f}",
              flush=True)
        report_phase(f"mesh-{kernel}-{mode}", counter, t0)
        require(np.isfinite(got).all() and err <= MESH_TOL,
                f"mesh {kernel}-{mode}: logits error {err:.3e}")
    # the replay's engines share the weights sharded above
    del params
    replay("mesh-serve", serve_argv(seed, "--mesh", "1,4"),
           (cfg, sharded), counter)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.kernels import default_interpret
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repository's src/ is not beside this "
              f"script ({e})", file=sys.stderr)
        return 2
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform "
              f"{dev.platform!r} ({dev.device_kind})", file=sys.stderr)
        return 1
    if default_interpret():
        print("chip_smoke: Pallas kernels would run in interpret mode",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 1
    print(f"[device] {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"compile cache at {enable_compile_cache()}", flush=True)
    counter = CompileCounter()
    try:
        if args.chips == 4:
            run_mesh(args.seed, counter)
        else:
            run_one_chip(args.seed, counter)
    except PhaseFailed as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
