"""Weights the benchmark serves, drawn from the run seed.

The base model comes from one jitted call on the device, in the dtype it
is served in and in the parameter layout the program consumes
(``{"embed", "ln_f", "lm_head", "blocks": {"ln1", "ln2", "attn", "ffn"}}``
with the layer axis leading). The plain reference draws the same
weights again with the same functions after the program's state is
freed, so it takes nothing the program made.

Adapters: the program materializes each adapter of a bank from a key
(``repro.lora.adapter.init_adapter``) with B = 0, which would make every
LoRA delta in the served path exactly zero. ``served_adapters`` swaps
in this module's materialization for the duration of a run: A and B
are both drawn from a key of (run seed, adapter id), so an adapter has
the same weights on every server and after every bank rebuild.
"""
from __future__ import annotations

import contextlib
import functools
import zlib

import jax
import jax.numpy as jnp

# rms of each adapter's delta relative to its projection's base output
DELTA_SCALE = 0.5


def base_key(seed: int):
    return jax.random.fold_in(jax.random.PRNGKey(seed), 0x0BA5E)


def adapter_key(seed: int, adapter_id: str):
    return jax.random.fold_in(
        jax.random.fold_in(jax.random.PRNGKey(seed), 0xADA),
        zlib.crc32(adapter_id.encode()) & 0x7FFFFFFF)


def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def dims(cfg: dict) -> dict:
    H, Kv, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    return {"d": cfg["d_model"], "q": H * hd, "kv": Kv * hd,
            "ff": cfg["d_ff"], "V": cfg["vocab_size"],
            "L": cfg["n_layers"]}


def _params(cfg: dict, key, dtype):
    k = dims(cfg)
    d, L = k["d"], k["L"]
    ks = iter(jax.random.split(key, 16))
    blocks = {
        "ln1": 1.0 + _normal(next(ks), (L, d), 0.1, dtype),
        "ln2": 1.0 + _normal(next(ks), (L, d), 0.1, dtype),
        "attn": {
            "wq": _normal(next(ks), (L, d, k["q"]), d ** -0.5, dtype),
            "wk": _normal(next(ks), (L, d, k["kv"]), d ** -0.5, dtype),
            "wv": _normal(next(ks), (L, d, k["kv"]), d ** -0.5, dtype),
            "wo": _normal(next(ks), (L, k["q"], d), k["q"] ** -0.5, dtype),
        },
        "ffn": {
            "w1": _normal(next(ks), (L, d, k["ff"]), d ** -0.5, dtype),
            "w3": _normal(next(ks), (L, d, k["ff"]), d ** -0.5, dtype),
            "w2": _normal(next(ks), (L, k["ff"], d), k["ff"] ** -0.5,
                          dtype),
        },
    }
    if cfg["qkv_bias"]:
        blocks["attn"]["bq"] = _normal(next(ks), (L, k["q"]), 0.02, dtype)
        blocks["attn"]["bk"] = _normal(next(ks), (L, k["kv"]), 0.02, dtype)
        blocks["attn"]["bv"] = _normal(next(ks), (L, k["kv"]), 0.02, dtype)
    return {
        "embed": _normal(next(ks), (k["V"], d), d ** -0.5, dtype),
        "ln_f": 1.0 + _normal(next(ks), (d,), 0.1, dtype),
        "lm_head": _normal(next(ks), (d, k["V"]), d ** -0.5, dtype),
        "blocks": blocks,
    }


def frozen(cfg: dict) -> tuple:
    """The configuration's numbers and names as a hashable key."""
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in cfg.items()
                        if isinstance(v, (int, float, bool, str, list))))


@functools.lru_cache(maxsize=None)
def _params_fn(key: tuple, dtype):
    return jax.jit(lambda k: _params(dict(key), k, dtype))


def make_params(cfg: dict, seed: int, dtype=jnp.float32):
    """The base model's weights: one jitted call on the default device."""
    return _params_fn(frozen(cfg), jnp.dtype(dtype))(base_key(seed))


def target_dims(cfg: dict, target: str) -> tuple:
    """(in, out) width of a LoRA target projection."""
    k = dims(cfg)
    return {"q": (k["d"], k["q"]), "k": (k["d"], k["kv"]),
            "v": (k["d"], k["kv"]), "o": (k["q"], k["d"])}[target]


def _adapter(cfg: dict, rank: int, key, n_layers: int, dtype):
    out = {}
    for j, t in enumerate(cfg["lora_targets"]):
        din, dout = target_dims(cfg, t)
        ka, kb = jax.random.split(jax.random.fold_in(key, j))
        out[t] = {
            "A": _normal(ka, (n_layers, din, rank), din ** -0.5, dtype),
            "B": _normal(kb, (n_layers, rank, dout),
                         DELTA_SCALE / rank ** 0.5, dtype),
        }
    return out


@functools.lru_cache(maxsize=None)
def _adapter_fn(key: tuple, rank: int, n_layers: int, dtype):
    return jax.jit(lambda k: _adapter(dict(key), rank, k, n_layers, dtype))


def make_adapter(cfg: dict, seed: int, adapter_id: str, rank: int,
                 n_layers=None, dtype=jnp.float32):
    """``{target: {"A": (L, in, r), "B": (L, r, out)}}`` of one adapter."""
    return _adapter_fn(frozen(cfg), rank, n_layers or cfg["n_layers"],
                       jnp.dtype(dtype))(adapter_key(seed, adapter_id))


@contextlib.contextmanager
def served_adapters(cfg: dict, seed: int):
    """Within the block, every bank the program builds holds this
    module's adapters: ``adapter_key`` of the program's bank modules
    returns the adapter id itself, and ``init_adapter`` materializes the
    benchmark's weights for it."""
    from repro.lora import adapter as ad
    from repro.lora import bank as bk

    def key_of(_base_key, adapter_id):
        return adapter_id

    def init(_cfg, rank, adapter_id, n_layers=None, dtype=jnp.float32):
        return make_adapter(cfg, seed, adapter_id, rank, n_layers, dtype)

    saved = [(m, name, getattr(m, name)) for m in (ad, bk)
             for name in ("adapter_key", "init_adapter")]
    for m, name, _ in saved:
        setattr(m, name, key_of if name == "adapter_key" else init)
    try:
        yield
    finally:
        for m, name, fn in saved:
            setattr(m, name, fn)
