"""ShapeDtypeStruct input specs + NamedSharding assignments for every
(architecture x input shape) combination — the dry-run's stand-ins
(weak-type-correct, shardable, zero allocation).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import INPUT_SHAPES, LONG_CONTEXT_WINDOW
from repro.lora.adapter import init_bank
from repro.models import model as M
from repro.models.common import param_pspecs
from repro.training.optimizer import adamw_init

from .mesh import batch_axes, batch_shard_size

# Serving dry-runs carry a live LoRA bank (the paper's workload): 8
# adapters padded to rank 64 on every server.
DRYRUN_N_ADAPTERS = 8
DRYRUN_MAX_RANK = 64


def _bs(mesh, n_rows: int):
    """Batch sharding axes if divisible, else replicate."""
    ax = batch_axes(mesh)
    size = batch_shard_size(mesh)
    return ax if ax and n_rows % size == 0 else ()


def _axis_size(mesh, ax) -> int:
    if isinstance(ax, (tuple, list)):
        n = 1
        for a in ax:
            n *= mesh.shape[a]
        return n
    return mesh.shape[ax]


def fit_spec(mesh, spec: P, shape) -> P:
    """Drop sharding on dims the mesh axes don't evenly divide (jit
    argument shardings, unlike constraints, require divisibility)."""
    dims = list(spec) + [None] * (len(shape) - len(spec))
    out = []
    for size, ax in zip(shape, dims):
        if ax is None:
            out.append(None)
        else:
            out.append(ax if size % _axis_size(mesh, ax) == 0 else None)
    return P(*out)


def fitted_ns(mesh, spec: P, leaf) -> NamedSharding:
    return NamedSharding(mesh, fit_spec(mesh, spec, leaf.shape))


def sds(tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def abstract_params(cfg, dtype=jnp.bfloat16):
    return jax.eval_shape(
        lambda: M.init_params(cfg, jax.random.PRNGKey(0), dtype))


def abstract_bank(cfg, dtype=jnp.bfloat16):
    n_layers = 1 if cfg.family == "hybrid" else cfg.n_layers
    if cfg.family == "vlm":
        return None          # LoRA rides the serving archs; vlm self-stack
    ranks = [DRYRUN_MAX_RANK] * DRYRUN_N_ADAPTERS
    return jax.eval_shape(
        lambda: init_bank(cfg, ranks, jax.random.PRNGKey(0),
                          n_layers=n_layers, dtype=dtype))


def param_shardings(mesh, params):
    specs = param_pspecs(params)
    return jax.tree.map(lambda s, p: fitted_ns(mesh, s, p), specs, params,
                        is_leaf=lambda x: isinstance(x, P))


def _ns(mesh, *spec):
    return NamedSharding(mesh, P(*spec))


def _cache_sharding(mesh, cfg, cache, batch):
    from repro.models.common import SHARDING_MODE
    ba = _bs(mesh, batch)
    b = ba if ba else None
    # k/v: (L, B, S, Kv*hd), a token's KV heads side by side
    if SHARDING_MODE == "baseline":
        kv_spec = P(None, b, None, "model")     # kv-head sharded
    else:
        # §Perf iter 1: shard the sequence dim over the model axis
        # (context-parallel decode) — always divisible, cuts per-device
        # cache 16x and removes the kv-head reshard storm.
        kv_spec = P(None, b, "model", None)
    by_key = {
        "pos": P(b),
        "k": kv_spec,
        "v": kv_spec,
        "xk": P(None, b, None, "model", None),
        "xv": P(None, b, None, "model", None),
        "c": P(None, b, None, None) if SHARDING_MODE == "baseline"
        else P(None, b, "model", None),
        "kr": P(None, b, None, None) if SHARDING_MODE == "baseline"
        else P(None, b, "model", None),
        "ssm": P(None, b, "model", None, None),
        "wkv": P(None, b, "model", None, None),
        "x_tm": P(None, b, None),
        "x_cm": P(None, b, None),
    }
    return {k: fitted_ns(mesh, by_key[k], cache[k]) for k in cache}


def _bank_sharding(mesh, bank):
    if bank is None:
        return None

    def leaf(path, x):
        name = None
        for e in reversed(path):
            if isinstance(e, jax.tree_util.DictKey):
                name = str(e.key)
                break
        if name == "A":
            return fitted_ns(mesh, P(None, None, None, "model"), x)
        return fitted_ns(mesh, P(None, None, "model", None), x)

    return jax.tree_util.tree_map_with_path(leaf, bank)


def _frontend_spec(cfg, batch, dtype=jnp.bfloat16):
    if cfg.family == "vlm":
        return jax.ShapeDtypeStruct(
            (batch, cfg.n_frontend_tokens, cfg.d_model), dtype)
    if cfg.family == "audio":
        return jax.ShapeDtypeStruct(
            (batch, cfg.encoder.n_frames, cfg.d_model), dtype)
    return None


def needs_window(cfg) -> bool:
    """long_500k carve-out: SSM state is O(1); everything attention-bearing
    uses the sliding-window variant."""
    return cfg.family != "ssm"


def effective_config(cfg, shape_name: str):
    if shape_name == "long_500k" and needs_window(cfg):
        return cfg.with_sliding_window(LONG_CONTEXT_WINDOW)
    return cfg


def build_case(cfg, shape_name: str, mesh, dtype=jnp.bfloat16):
    """Returns (fn, example_args (ShapeDtypeStructs), in_shardings,
    donate_argnums) for jit(fn).lower(*args)."""
    shape = INPUT_SHAPES[shape_name]
    cfg = effective_config(cfg, shape_name)
    B, S = shape.global_batch, shape.seq_len
    ba = _bs(mesh, B)
    b = ba if ba else None
    params = abstract_params(cfg, dtype)
    p_sh = param_shardings(mesh, params)
    tok_sh = _ns(mesh, b, None)

    if shape.mode == "train":
        from repro.training.optimizer import AdamWConfig
        from repro.training.train_step import make_train_step
        opt = jax.eval_shape(lambda: adamw_init(params))
        opt_sh = {"mu": param_shardings(mesh, opt["mu"]),
                  "nu": param_shardings(mesh, opt["nu"]),
                  "step": _ns(mesh)}
        batch = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
                 "labels": jax.ShapeDtypeStruct((B, S), jnp.int32)}
        batch_sh = {"tokens": tok_sh, "labels": tok_sh}
        fe = _frontend_spec(cfg, B, dtype)
        if fe is not None:
            batch["frontend"] = fe
            batch_sh["frontend"] = _ns(mesh, b, None, None)
        step = make_train_step(cfg, AdamWConfig(), remat=True)
        return (step, (sds(params), sds(opt), batch),
                (p_sh, opt_sh, batch_sh), (0, 1))

    bank = abstract_bank(cfg, dtype)
    bank_sh = _bank_sharding(mesh, bank)
    idx = jax.ShapeDtypeStruct((B,), jnp.int32)
    idx_sh = _ns(mesh, b)

    if shape.mode == "prefill":
        tokens = jax.ShapeDtypeStruct((B, S), jnp.int32)
        fe = _frontend_spec(cfg, B, dtype)

        if bank is not None:
            def fn(params, tokens, bank, lora_idx, frontend=None):
                return M.prefill(cfg, params, tokens, frontend=frontend,
                                 bank=bank, lora_idx=lora_idx,
                                 cache_dtype=dtype)
            args = [sds(params), tokens, sds(bank), idx]
            shs = [p_sh, tok_sh, bank_sh, idx_sh]
        else:
            def fn(params, tokens, frontend=None):
                return M.prefill(cfg, params, tokens, frontend=frontend,
                                 cache_dtype=dtype)
            args = [sds(params), tokens]
            shs = [p_sh, tok_sh]
        if fe is not None:
            args.append(fe)
            shs.append(_ns(mesh, b, None, None))
        return fn, tuple(args), tuple(shs), ()

    # decode: one new token against a seq_len cache
    cache_len = min(S, cfg.sliding_window) if cfg.sliding_window else S
    enc_len = (cfg.encoder.n_frames if cfg.encoder else
               (cfg.n_frontend_tokens or None))
    cache = jax.eval_shape(
        lambda: M.init_cache(cfg, B, cache_len, dtype, enc_len=enc_len))
    cache_sh = _cache_sharding(mesh, cfg, cache, B)
    tokens = jax.ShapeDtypeStruct((B,), jnp.int32)

    if bank is not None:
        def fn(params, cache, tokens, bank, lora_idx):
            return M.decode_step(cfg, params, cache, tokens, bank=bank,
                                 lora_idx=lora_idx)
        args = (sds(params), cache, tokens, sds(bank), idx)
        shs = (p_sh, cache_sh, _ns(mesh, b), bank_sh, idx_sh)
    else:
        def fn(params, cache, tokens):
            return M.decode_step(cfg, params, cache, tokens)
        args = (sds(params), cache, tokens)
        shs = (p_sh, cache_sh, _ns(mesh, b))
    return fn, args, shs, (1,)
