"""Batched heterogeneous-adapter application.

Two execution paths, each with a padded and a bucketed form:
  * gather-einsum (default, lowerable on any backend; used by the dry-run
    and the CPU engine) — padded: per-row adapter index gathers its A/B
    from the bank, everything padded to the bank's max rank (the paper's
    co-batch padding tax, faithfully); bucketed: one masked pass per rank
    bucket at the bucket's own rank (rows outside the bucket are zeroed),
    numerically identical to padded because padding is inert;
  * Pallas SGMV (``repro.kernels.ops``) — TPU kernel path. The fused v2
    kernels are jittable end-to-end, so they serve BOTH the token-major
    flattened entry point (``apply_bank_sgmv``) and the model's in-scan
    LoRA callback: ``make_lora_cb(..., kernel="sgmv")`` flattens the
    (B, S, d) activation to token-major rows and dispatches one fused
    kernel per target — ``sgmv_fused`` for padded banks,
    ``sgmv_bucketed_fused`` (single dispatch, every bucket at its own
    rank) for bucketed banks.

Every delta the callback adds runs under ``jax.named_scope("lora")``,
so a device trace can tell the LoRA work of a step from the rest.

``make_lora_cb`` is layout-polymorphic: a dict bank slice selects the
padded path with ``idx: (Bt,)`` global adapter rows; a tuple of per-
bucket slices selects the bucketed path with ``idx: (Bt, 2)`` carrying
(bucket, local-row) per request — the shape ``LoRABank.lora_idx``
produces. The hook also gives each row's own factors of a target
(``factors(name)``), for a delta that enters the model other than as
``x A B`` (MLA's absorbed ``kv_b``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.common import constrain, rows_to_tokens, tokens_to_rows


def lora_delta(x, A, B, idx, scaling: float = 1.0):
    """x: (Bt, S, d); A: (Na, d, r); B: (Na, r, out); idx: (Bt,) int32.

    Every row pays max-rank (r = bank rank) cost regardless of its
    adapter's true rank — zero-padded banks make the extra columns
    numerically inert but computationally present (BGMV semantics).
    """
    from repro.models.common import SHARDING_MODE, current_axis_env
    coshard = current_axis_env().lora == "coshard"
    a = A[idx]                                   # (Bt, d, r)
    b = B[idx]                                   # (Bt, r, out)
    h = jnp.einsum("bsd,bdr->bsr", x, a.astype(x.dtype))
    if coshard:
        # mesh-sharded engine: A is d-sharded, so each shard holds a
        # partial rank-r sum — replicating h here is ONE psum of the
        # tiny (Bt, S, r) intermediate, never the (Bt, S, out) delta
        h = constrain(h, "batch", None, None)
    elif SHARDING_MODE == "baseline":
        # S-LoRA TP: rank dim sharded -> partial sums all-reduced
        h = constrain(h, "batch", None, "model")
    out = jnp.einsum("bsr,bro->bso", h, b.astype(x.dtype))
    if coshard:
        # B is d_out-sharded: the delta comes out column-sharded exactly
        # like the base projection output it is added to — no gather
        return constrain(out * scaling, "batch", None, "model")
    return constrain(out * scaling, "batch", None, None)


def lora_delta_bucketed(x, bucket_targets, idx, scaling: float = 1.0):
    """x: (Bt, S, d); bucket_targets: sequence of per-bucket {"A","B"}
    slices (bucket b at rank r_b); idx: (Bt, 2) int32 of (bucket, local).

    Each bucket runs a gather-einsum at its own rank over the full row
    set with out-of-bucket rows masked to zero — static shapes, and each
    row's *numerics* come only from its own bucket.  (The compute saving
    of bucketing lives on the token-compacting SGMV path and in the cost
    model; this dense form trades a masked pass per bucket for backend
    portability.)
    """
    bucket, local = idx[..., 0], idx[..., 1]
    out = None
    for b, t in enumerate(bucket_targets):
        sel = bucket == b
        y = lora_delta(x, t["A"], t["B"], jnp.where(sel, local, 0), scaling)
        y = jnp.where(sel[:, None, None], y, 0.0)
        out = y if out is None else out + y
    return out


def _coshard_env():
    """The active mesh-sharded LoRA environment, or None. Returns
    (mesh, model_axis, n_shards) when the engine runs in "coshard" mode
    with a real model axis to split over."""
    from repro.models.common import current_axis_env
    env = current_axis_env()
    if env.lora != "coshard" or env.mesh is None or env.model is None:
        return None
    s = env.mesh.shape[env.model]
    if s <= 1:
        return None
    return env.mesh, env.model, s


def _lora_delta_sgmv(x, target, idx, scaling, block_t, interpret):
    """Padded-bank fused-kernel form of ``lora_delta``: token-major
    flatten, one ``sgmv_fused`` dispatch, unflatten. Under the mesh-
    sharded engine ("coshard" axis env) the dispatch becomes a
    shard_map: each shard runs the shrink kernel on its local
    d/n_shards slice of A, the (T_pad, r) partials are reduced with ONE
    psum, and the expand kernel emits the d_out-sharded delta — full
    weights and the full-width delta never materialize on one device."""
    from repro.kernels.ops import padded_len, prepare_segments, sgmv_fused
    x2, (B_, S_) = rows_to_tokens(x)
    tok = jnp.repeat(idx, S_)
    bt = 16 if block_t is None else block_t
    A = target["A"].astype(x.dtype)
    B = target["B"].astype(x.dtype)
    co = _coshard_env()
    if co is not None and A.shape[1] % co[2] == 0 \
            and B.shape[2] % co[2] == 0:
        import jax
        from jax.sharding import PartitionSpec as P
        from repro.kernels import resolve_interpret
        from repro.kernels.sgmv import sgmv_expand, sgmv_shrink
        mesh, axis, _ = co
        T, d = x2.shape
        Na = A.shape[0]
        dest, block_adapter = prepare_segments(tok, Na, bt)
        x_pad = jnp.zeros((padded_len(T, Na, bt), d), x.dtype
                          ).at[dest].set(x2)
        interp = resolve_interpret(interpret)

        def per_shard(xp, As, Bs, blk):
            h = sgmv_shrink(xp, As, blk, block_t=bt, interpret=interp)
            h = jax.lax.psum(h, axis)
            return sgmv_expand(h, Bs, blk, block_t=bt, interpret=interp)

        y_pad = jax.shard_map(
            per_shard, mesh=mesh,
            in_specs=(P(None, axis), P(None, axis, None),
                      P(None, None, axis), P(None)),
            out_specs=P(None, axis), check_vma=False,
        )(x_pad, A, B, block_adapter)
        y = y_pad[dest] * scaling
        return constrain(tokens_to_rows(y, B_, S_), "batch", None,
                         "model")
    y = sgmv_fused(x2, A, B, tok, scaling=scaling, block_t=bt,
                   interpret=interpret)
    return constrain(tokens_to_rows(y, B_, S_), "batch", None, None)


def _lora_delta_sgmv_bucketed(x, bucket_targets, idx, scaling, block_t,
                              interpret):
    """Bucketed fused-kernel form: every batch row is its own "adapter"
    (adapter_bucket/adapter_local taken straight from the (Bt, 2) idx),
    so the whole heterogeneous delta is ONE ``sgmv_bucketed_fused``
    dispatch with each row's tokens at its own bucket's rank. Under the
    "coshard" axis env the dispatch is a shard_map over the split
    multibank kernels: per-shard shrink on local d slices of every
    bucket's A bank, one psum of the (T_pad, max_r) intermediate, then
    the expand kernel against local d_out slices of the B banks (see
    the per-shard reduction contract in ``repro.kernels.sgmv``)."""
    from repro.kernels.ops import (padded_len, prepare_segments_bucketed,
                                   sgmv_bucketed_fused)
    x2, (B_, S_) = rows_to_tokens(x)
    tok = jnp.repeat(jnp.arange(B_, dtype=jnp.int32), S_)
    co = _coshard_env()
    if co is not None \
            and all(t["A"].shape[1] % co[2] == 0
                    and t["B"].shape[2] % co[2] == 0
                    for t in bucket_targets):
        import jax
        from jax.sharding import PartitionSpec as P
        from repro.kernels import resolve_interpret
        from repro.kernels.sgmv import (sgmv_multibank_expand,
                                        sgmv_multibank_shrink)
        mesh, axis, _ = co
        bt = 16 if block_t is None else block_t
        T, d = x2.shape
        Na = B_
        nb = len(bucket_targets)
        dest, block_adapter = prepare_segments_bucketed(
            tok, idx[:, 0], Na, nb, bt)
        block_bucket = idx[:, 0][block_adapter]
        block_row = idx[:, 1][block_adapter]
        x_pad = jnp.zeros((padded_len(T, Na, bt), d), x.dtype
                          ).at[dest].set(x2)
        A_banks = tuple(t["A"].astype(x.dtype) for t in bucket_targets)
        B_banks = tuple(t["B"].astype(x.dtype) for t in bucket_targets)
        interp = resolve_interpret(interpret)

        def per_shard(xp, As, Bs, bkt, row):
            h = sgmv_multibank_shrink(xp, As, bkt, row, block_t=bt,
                                      interpret=interp)
            h = jax.lax.psum(h, axis)
            return sgmv_multibank_expand(h, Bs, bkt, row, block_t=bt,
                                         interpret=interp)

        y_pad = jax.shard_map(
            per_shard, mesh=mesh,
            in_specs=(P(None, axis),
                      tuple(P(None, axis, None) for _ in A_banks),
                      tuple(P(None, None, axis) for _ in B_banks),
                      P(None), P(None)),
            out_specs=P(None, axis), check_vma=False,
        )(x_pad, A_banks, B_banks, block_bucket, block_row)
        y = y_pad[dest] * scaling
        return constrain(tokens_to_rows(y, B_, S_), "batch", None,
                         "model")
    banks = tuple((t["A"].astype(x.dtype), t["B"].astype(x.dtype))
                  for t in bucket_targets)
    y = sgmv_bucketed_fused(x2, banks, tok, idx[:, 0], idx[:, 1],
                            scaling=scaling, block_t=block_t,
                            interpret=interpret)
    return constrain(tokens_to_rows(y, B_, S_), "batch", None, None)


def make_lora_cb(bank_layer, idx, scaling: float = 1.0, *,
                 kernel: str = "einsum", block_t=None,
                 interpret=None):
    """Bind one layer's bank slice and per-row adapter indices into the
    projection hook used by the attention/ssm blocks.

    ``bank_layer`` is {target: {"A","B"}} for a padded bank, or a tuple
    of such dicts (one per rank bucket) for a bucketed bank; ``idx`` is
    the matching ``LoRABank.lora_idx`` output. ``kernel`` selects the
    execution form: "einsum" (gather-einsum, any backend) or "sgmv"
    (fused Pallas kernels over the token-major flattening — jittable, so
    it works inside the layer scan; compiled on TPU, interpreted
    elsewhere per ``repro.kernels.default_interpret``). ``block_t=None``
    defers to the ``kernels.tune`` heuristic table (bucketed path) or
    the default 16 (padded path)."""
    if bank_layer is None:
        return None
    if kernel not in ("einsum", "sgmv"):
        raise ValueError(f"unknown lora kernel {kernel!r}")

    if isinstance(bank_layer, (tuple, list)):
        def cb_bucketed(name, x):
            targets = [bk.get(name) for bk in bank_layer]
            if any(t is None for t in targets):
                return 0.0
            with jax.named_scope("lora"):
                if kernel == "sgmv":
                    return _lora_delta_sgmv_bucketed(
                        x, targets, idx, scaling, block_t, interpret)
                return lora_delta_bucketed(x, targets, idx, scaling)

        def factors_bucketed(name):
            if any(bk.get(name) is None for bk in bank_layer):
                return []
            bucket, local = idx[..., 0], idx[..., 1]
            out = []
            for b, bk in enumerate(bank_layer):
                rows = jnp.where(bucket == b, local, 0)
                out.append((bk[name]["A"][rows],
                            bk[name]["B"][rows] * scaling, bucket == b))
            return out

        cb_bucketed.factors = factors_bucketed
        return cb_bucketed

    def cb(name, x):
        t = bank_layer.get(name)
        if t is None:
            return 0.0
        with jax.named_scope("lora"):
            if kernel == "sgmv":
                return _lora_delta_sgmv(x, t, idx, scaling, block_t,
                                        interpret)
            return lora_delta(x, t["A"], t["B"], idx, scaling)

    def factors(name):
        """[(A (Bt, in, r), B (Bt, r, out), row mask or None)]: each
        row's adapter factors of target ``name``, B scaled."""
        t = bank_layer.get(name)
        if t is None:
            return []
        return [(t["A"][idx], t["B"][idx] * scaling, None)]

    cb.factors = factors
    return cb


def apply_bank_sgmv(x, bank, name: str, layer: int, token_adapter, *,
                    scaling: float = 1.0, block_t=None,
                    interpret=None, fused: bool = True):
    """Pallas path for token-major flattened layouts: x: (T, d) tokens,
    token_adapter: (T,) *global* adapter rows of ``bank`` (a LoRABank).

    Padded banks dispatch one ``sgmv_fused`` over the full token set at
    the bank max rank; bucketed banks dispatch ``sgmv_bucketed_fused``,
    a SINGLE traced kernel sweep in which each bucket's tokens run at
    the bucket's own rank (FLOPs = sum_b T_b * r_b * (d + o) instead of
    T * max_r * (d + o)). ``fused=False`` selects the legacy two-kernel
    / host-loop dispatchers (kept for A/Bs; bit-identical outputs).
    """
    from repro.kernels.ops import (sgmv, sgmv_bucketed_fused, sgmv_fused,
                                   sgmv_rank_bucketed)
    if bank.mode == "padded":
        t = bank.data[name]
        fn = sgmv_fused if fused else sgmv
        return fn(x, t["A"][layer], t["B"][layer], token_adapter,
                  scaling=scaling,
                  block_t=16 if block_t is None else block_t,
                  interpret=interpret)
    banks = [(bk[name]["A"][layer], bk[name]["B"][layer])
             for bk in bank.data]
    if fused:
        return sgmv_bucketed_fused(x, banks, token_adapter,
                                   bank.adapter_bucket,
                                   bank.adapter_local, scaling=scaling,
                                   block_t=block_t, interpret=interpret)
    return sgmv_rank_bucketed(x, banks, token_adapter, bank.adapter_bucket,
                              adapter_local=bank.adapter_local,
                              scaling=scaling,
                              block_t=16 if block_t is None else block_t,
                              interpret=interpret)
