"""LoRABank: one descriptor for a server's stacked adapter bank, in
either of two layouts.

``padded`` — the paper-faithful baseline: every adapter zero-padded to
the hosted subset's max rank, one stacked bank, every co-batched request
pays max-rank compute (§III-A.5's padding tax, reproduced faithfully).

``bucketed`` — the beyond-paper mode: adapters grouped into power-of-two
rank buckets, each bucket its own stacked bank at the *bucket* rank.  A
rank-8 request co-batched with a rank-128 one pays rank-8 compute on the
bucketed paths (CaraServe-style rank-aware serving).  Both layouts hold
numerically identical adapter weights (padding is inert), so switching
``bank_mode`` changes cost, never tokens.

``LoRABank.data`` is what the model consumes:
  padded   — {target: {"A": (L, Na, d, r), "B": (L, Na, r, o)}}
  bucketed — tuple of such pytrees, one per bucket (ascending bucket
             rank), each stacked over only that bucket's adapters at the
             bucket's rank.
Both thread through ``lax.scan`` over the layer axis unchanged (a tuple
of pytrees is itself a pytree).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .adapter import adapter_key, bank_nbytes, init_adapter, pad_rank


def rank_bucket(rank: int) -> int:
    """Smallest power of two >= rank (bucket 8 serves ranks 5..8)."""
    if rank <= 0:
        raise ValueError(f"rank must be positive, got {rank}")
    return 1 << (rank - 1).bit_length()


@dataclasses.dataclass(frozen=True)
class LoRABank:
    """Descriptor + device data for one server's hosted adapter subset."""
    mode: str                          # "padded" | "bucketed"
    adapter_ids: Tuple[str, ...]       # sorted; index = model adapter idx
    ranks: Tuple[int, ...]             # aligned with adapter_ids
    data: Any                          # model-facing bank pytree(s)
    bucket_ranks: Tuple[int, ...] = () # ascending; empty for padded
    bucket_counts: Tuple[int, ...] = ()  # adapters per bucket
    adapter_bucket: Optional[jax.Array] = None   # (Na,) adapter -> bucket
    adapter_local: Optional[jax.Array] = None    # (Na,) row within bucket

    # -- introspection ---------------------------------------------------
    @property
    def n_adapters(self) -> int:
        return len(self.adapter_ids)

    @property
    def max_rank(self) -> int:
        return max(self.ranks)

    @property
    def signature(self) -> tuple:
        """Layout identity for jit-cache keys: prefill functions traced
        against one signature are reusable until the bank reshapes."""
        if self.mode == "padded":
            return ("padded", self.max_rank, self.n_adapters)
        return ("bucketed",
                tuple(zip(self.bucket_ranks, self.bucket_counts)))

    def nbytes(self) -> int:
        return bank_nbytes(self.data)

    def index(self, adapter_id: str) -> int:
        return self.adapter_ids.index(adapter_id)

    # -- model-facing indices -------------------------------------------
    def lora_idx(self, adapter_idx: jax.Array) -> jax.Array:
        """Turn global adapter indices (B,) into the index array the
        model callback consumes: the same (B,) for padded, a stacked
        (B, 2) of (bucket, local-row) for bucketed."""
        adapter_idx = jnp.asarray(adapter_idx, jnp.int32)
        if self.mode == "padded":
            return adapter_idx
        return jnp.stack([self.adapter_bucket[adapter_idx],
                          self.adapter_local[adapter_idx]], axis=-1)

    # -- per-adapter weight access (GDR remote-read data plane) ----------
    def _rows(self, adapter_id: str):
        """(bank pytree holding the adapter, its stack row, its rank)."""
        i = self.index(adapter_id)
        r = self.ranks[i]
        if self.mode == "padded":
            return self.data, i, r
        return (self.data[int(self.adapter_bucket[i])],
                int(self.adapter_local[i]), r)

    def get_adapter(self, adapter_id: str):
        """Extract one adapter's unpadded weights
        ``{target: {"A": (L, d, r), "B": (L, r, o)}}`` — what a peer
        serves over GDR when this bank's copy is read remotely."""
        tree, row, r = self._rows(adapter_id)
        return {t: {"A": tree[t]["A"][:, row, :, :r],
                    "B": tree[t]["B"][:, row, :r, :]}
                for t in tree}

    def set_adapter(self, adapter_id: str, weights) -> "LoRABank":
        """Return a bank with ``adapter_id``'s rows overwritten by
        ``weights`` (the peer-read install path; padding beyond the
        adapter's rank is untouched and must stay zero)."""
        tree, row, r = self._rows(adapter_id)
        new = {t: {"A": tree[t]["A"].at[:, row, :, :r].set(
                       weights[t]["A"]),
                   "B": tree[t]["B"].at[:, row, :r, :].set(
                       weights[t]["B"])}
               for t in tree}
        if self.mode == "padded":
            return dataclasses.replace(self, data=new)
        b = int(self.adapter_bucket[self.index(adapter_id)])
        data = tuple(new if j == b else d
                     for j, d in enumerate(self.data))
        return dataclasses.replace(self, data=data)


def build_bank(cfg, adapter_ranks: Dict[str, int], key, *,
               mode: str = "padded", n_layers=None,
               dtype=jnp.float32) -> LoRABank:
    """Build a bank over ``sorted(adapter_ranks)`` in the given layout.

    Weights are keyed per adapter id via ``adapter_key`` in both modes,
    so the same adapter carries bit-identical weights whether it lands in
    a padded bank, a bucketed bank, or a rebuilt bank after a placement
    change — the parity guarantee the padded-vs-bucketed A/Bs rest on.
    """
    ids = sorted(adapter_ranks)
    if not ids:
        raise ValueError("build_bank needs at least one adapter")
    ranks = [adapter_ranks[a] for a in ids]
    if mode == "padded":
        from .adapter import init_bank_from
        data = init_bank_from(cfg, adapter_ranks, key, n_layers=n_layers,
                              dtype=dtype)
        return LoRABank("padded", tuple(ids), tuple(ranks), data)
    if mode != "bucketed":
        raise ValueError(f"unknown bank_mode {mode!r}")

    buckets = sorted({rank_bucket(r) for r in ranks})
    members: Dict[int, list] = {b: [] for b in buckets}
    bucket_of, local_of = [], []
    for aid, r in zip(ids, ranks):
        b = rank_bucket(r)
        bucket_of.append(buckets.index(b))
        local_of.append(len(members[b]))
        members[b].append(aid)
    data = []
    for b in buckets:
        singles = []
        for aid in members[b]:
            a = init_adapter(cfg, adapter_ranks[aid], adapter_key(key, aid),
                             n_layers=n_layers, dtype=dtype)
            singles.append(pad_rank(a, b))
        data.append(jax.tree.map(lambda *xs: jnp.stack(xs, axis=1),
                                 *singles))
    return LoRABank("bucketed", tuple(ids), tuple(ranks), tuple(data),
                    bucket_ranks=tuple(buckets),
                    bucket_counts=tuple(len(members[b]) for b in buckets),
                    adapter_bucket=jnp.asarray(bucket_of, jnp.int32),
                    adapter_local=jnp.asarray(local_of, jnp.int32))
