"""LoRA adapters: single-adapter pytrees and stacked multi-adapter banks.

A *bank* holds ``n_adapters`` adapters padded to a common ``max_rank`` —
exactly the layout Punica/S-LoRA kernels consume, and the layout in which
the padding tax the paper analyzes (§III-A.5) arises: every request in a
co-batch pays ``max_rank`` compute. Adapters of rank r < max_rank are
zero-padded (rows/cols beyond r contribute nothing numerically but fully
participate in the matmuls).
"""
from __future__ import annotations

import dataclasses
import zlib
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models.common import dense_init


@dataclasses.dataclass(frozen=True)
class Adapter:
    """Metadata for one serving adapter (the unit the orchestrator places)."""
    adapter_id: str
    rank: int
    base_model: str = "llama-7b-paper"

    def nbytes(self, cfg) -> int:
        """Host-memory footprint (bf16): A+B on every target, all layers."""
        total = 0
        for t in cfg.lora.targets:
            in_dim = _target_in_dim(cfg, t)
            out_dim = _target_out_dim(cfg, t)
            total += in_dim * self.rank + self.rank * out_dim
        return 2 * total * cfg.n_layers  # 2 bytes / param


def _target_out_dim(cfg, target: str) -> int:
    hd = cfg.resolved_head_dim or cfg.d_model
    H, Kv = cfg.n_heads or 1, cfg.n_kv_heads or 1
    if cfg.ssm is not None and cfg.ssm.kind == "rwkv6":
        return cfg.d_model
    if cfg.mla is not None:
        # the release's q_proj, kv_a_proj_with_mqa (latent and rope key),
        # kv_b_proj (per head [k_nope | v]) and o_proj
        m = cfg.mla
        return {"q": H * (m.qk_nope_head_dim + m.qk_rope_head_dim),
                "kv_a": m.kv_lora_rank + m.qk_rope_head_dim,
                "kv_b": H * (m.qk_nope_head_dim + m.v_head_dim),
                "o": cfg.d_model}[target]
    return {"q": H * hd, "k": Kv * hd, "v": Kv * hd, "o": cfg.d_model}[target]


def _target_in_dim(cfg, target: str) -> int:
    if target == "kv_b":
        return cfg.mla.kv_lora_rank
    if target != "o":
        return cfg.d_model
    if cfg.ssm is not None and cfg.ssm.kind == "rwkv6":
        return cfg.d_model
    if cfg.mla is not None:
        return cfg.n_heads * cfg.mla.v_head_dim
    return cfg.n_heads * cfg.resolved_head_dim


def init_adapter(cfg, rank: int, key, n_layers=None, dtype=jnp.float32):
    """Single adapter: {target: {"A": (L,d,r), "B": (L,r,out)}}.

    A ~ N(0, 1/d); B = 0 (standard LoRA init).
    """
    L = n_layers if n_layers is not None else cfg.n_layers
    d = cfg.d_model
    out = {}
    for t in cfg.lora.targets:
        key, ka = jax.random.split(key)
        o = _target_out_dim(cfg, t)
        in_dim = _target_in_dim(cfg, t)
        out[t] = {
            "A": dense_init(ka, (L, in_dim, rank), fan_in=in_dim, dtype=dtype),
            "B": jnp.zeros((L, rank, o), dtype),
        }
    return out


def init_bank(cfg, ranks, key, n_layers=None, dtype=jnp.float32):
    """Stacked bank: {target: {"A": (L, Na, d, max_r), "B": (L, Na, max_r, o)}}.

    Adapters with rank < max(ranks) are zero-padded to max rank — the
    max-rank padding semantics of BGMV/MBGMV.
    """
    max_r = max(ranks)
    singles = []
    for r in ranks:
        key, k2 = jax.random.split(key)
        a = init_adapter(cfg, r, k2, n_layers=n_layers, dtype=dtype)
        singles.append(pad_rank(a, max_r))
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=1), *singles)


def adapter_key(base_key, adapter_id: str):
    """Deterministic per-adapter PRNG key: the same adapter id always
    yields the same weights, no matter which bank subset it lands in."""
    return jax.random.fold_in(base_key,
                              zlib.crc32(adapter_id.encode()) & 0x7FFFFFFF)


def init_bank_from(cfg, adapter_ranks: Dict[str, int], key, n_layers=None,
                   dtype=jnp.float32):
    """Bank over ``sorted(adapter_ranks)``, padded to the *subset's* max
    rank (not a global one): a server hosting only ranks {8, 16} pays a
    16-wide bank. Weights are keyed per adapter id via ``adapter_key``,
    so rebuilding a bank for a different hosted subset (after a
    placement change) reproduces identical weights for every adapter it
    keeps."""
    ids = sorted(adapter_ranks)
    if not ids:
        raise ValueError("init_bank_from needs at least one adapter")
    max_r = max(adapter_ranks.values())
    singles = []
    for aid in ids:
        a = init_adapter(cfg, adapter_ranks[aid], adapter_key(key, aid),
                         n_layers=n_layers, dtype=dtype)
        singles.append(pad_rank(a, max_r))
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=1), *singles)


def pad_rank(adapter, max_r: int):
    """Zero-pad one adapter to rank ``max_r``: every target's A
    (L, in, r) along its last axis and B (L, r, out) along its middle
    one."""
    def pad(w, axis):
        widths = [(0, 0)] * w.ndim
        widths[axis] = (0, max_r - w.shape[axis])
        return jnp.pad(w, widths)

    return {t: {"A": pad(w["A"], -1), "B": pad(w["B"], -2)}
            for t, w in adapter.items()}


def merge_adapter(params, adapter, cfg, scaling: float = 1.0):
    """Merge a single adapter into base weights (the paper's §II-B note:
    zero-overhead serving for very hot adapters merged into a dedicated
    instance)."""
    import copy
    merged = jax.tree.map(lambda x: x, params)  # shallow structural copy
    name_map = {"q": "wq", "k": "wk", "v": "wv", "o": "wo"}
    blocks = merged.get("blocks")
    if blocks is None:
        raise ValueError("merge_adapter supports uniform-stack archs")
    attn = dict(blocks["attn"])
    for t, w_name in name_map.items():
        if t not in adapter:
            continue
        delta = jnp.einsum("ldr,lro->ldo", adapter[t]["A"], adapter[t]["B"])
        if w_name in attn:
            attn[w_name] = attn[w_name] + scaling * delta.astype(
                attn[w_name].dtype)
    blocks = dict(blocks)
    blocks["attn"] = attn
    merged = dict(merged)
    merged["blocks"] = blocks
    return merged


def bank_nbytes(bank) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(bank))
