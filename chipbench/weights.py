"""Weights the benchmark serves, drawn from the run seed.

The base model comes from one jitted call on the device, in the dtype it
is served in and in the parameter layout the program consumes, drawn by
the architecture module's ``params`` (``chipbench/arch``). The plain
reference draws the same weights again with the same functions after
the program's state is freed, so it takes nothing the program made.

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


def normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


class _Group(tuple):
    """A nested group of a frozen configuration."""


def _freeze(v):
    if isinstance(v, dict):
        return _Group(frozen(v))
    if isinstance(v, list):
        return tuple(_freeze(x) for x in v)
    return v


def frozen(cfg: dict) -> tuple:
    """The configuration's numbers and names as a hashable key, nested
    groups frozen the same way."""
    return tuple(sorted((k, _freeze(v)) for k, v in cfg.items()
                        if isinstance(v, (int, float, bool, str, list,
                                          dict))))


def _thaw(v):
    if isinstance(v, _Group):
        return thawed(v)
    if isinstance(v, tuple):
        return [_thaw(x) for x in v]
    return v


def thawed(key: tuple) -> dict:
    """The configuration ``frozen`` made ``key`` of."""
    return {k: _thaw(v) for k, v in key}


@functools.lru_cache(maxsize=None)
def _weights_fn(arch, key: tuple, dtype):
    return jax.jit(lambda k: arch.params(thawed(key), k, dtype))


def make_params(arch, cfg: dict, seed: int, dtype=jnp.float32):
    """The base model's weights (``arch.params``): one jitted call on the
    default device."""
    return _weights_fn(arch, frozen(cfg), jnp.dtype(dtype))(base_key(seed))


def _adapter(arch, cfg: dict, rank: int, key, n_layers: int, dtype):
    out = {}
    for j, t in enumerate(cfg["lora_targets"]):
        din, dout = arch.target_dims(cfg, t)
        ka, kb = jax.random.split(jax.random.fold_in(key, j))
        out[t] = {
            "A": normal(ka, (n_layers, din, rank), din ** -0.5, dtype),
            "B": normal(kb, (n_layers, rank, dout),
                        DELTA_SCALE / rank ** 0.5, dtype),
        }
    return out


@functools.lru_cache(maxsize=None)
def _adapter_fn(arch, key: tuple, rank: int, n_layers: int, dtype):
    return jax.jit(lambda k: _adapter(arch, thawed(key), rank, k, n_layers,
                                      dtype))


def make_adapter(arch, cfg: dict, seed: int, adapter_id: str, rank: int,
                 n_layers=None, dtype=jnp.float32):
    """``{target: {"A": (L, in, r), "B": (L, r, out)}}`` of one adapter,
    each target's widths from ``arch.target_dims``."""
    return _adapter_fn(arch, frozen(cfg), rank,
                       n_layers or cfg["n_layers"],
                       jnp.dtype(dtype))(adapter_key(seed, adapter_id))


@contextlib.contextmanager
def served_adapters(arch, cfg: dict, seed: int):
    """Within the block, every bank the program builds holds this
    module's adapters: ``adapter_key`` of the program's bank modules
    returns the adapter id itself, and ``init_adapter`` materializes the
    benchmark's weights for it."""
    from repro.lora import adapter as ad
    from repro.lora import bank as bk

    def key_of(_base_key, adapter_id):
        return adapter_id

    def init(_cfg, rank, adapter_id, n_layers=None, dtype=jnp.float32):
        return make_adapter(arch, cfg, seed, adapter_id, rank, n_layers,
                            dtype)

    saved = [(m, name, getattr(m, name)) for m in (ad, bk)
             for name in ("adapter_key", "init_adapter")]
    for m, name, _ in saved:
        setattr(m, name, key_of if name == "adapter_key" else init)
    try:
        yield
    finally:
        for m, name, fn in saved:
            setattr(m, name, fn)
