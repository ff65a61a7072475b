"""FFN layers: SwiGLU and Mixture-of-Experts.

Two MoE dispatch paths:
  * GSPMD sort-scatter (paper-faithful-first baseline): tokens sorted by
    expert, packed into (E, C, d) capacity buffers, expert dim sharded.
    The SPMD partitioner turns the global scatter into replication-scale
    collectives — the measured collective wall in §Perf pair 2.
  * shard_map expert-parallel (beyond-paper, §Perf iter 2): the sequence
    dim is already sharded over the model axis; each shard routes its own
    tokens locally, `all_to_all` exchanges capacity buffers so each shard
    runs only its E/n experts, and a reverse `all_to_all` brings outputs
    home. Collective volume drops from O(E*C*d) replication to
    O(K*N_local*d) exchange per layer.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .common import (SHARDING_MODE, constrain, constrain_resid,
                     current_axis_env, dense_init, dense_proj)


def init_swiglu(d: int, ff: int, key, dtype=jnp.float32, prefix=""):
    ks = jax.random.split(key, 3)
    shared = prefix == "s"
    return {
        ("ws1" if shared else "w1"): dense_init(ks[0], (d, ff), dtype=dtype),
        ("ws3" if shared else "w3"): dense_init(ks[1], (d, ff), dtype=dtype),
        ("ws2" if shared else "w2"): dense_init(ks[2], (ff, d), fan_in=ff,
                                                dtype=dtype),
    }


def swiglu(p, x, shared: bool = False, proj=None):
    """SwiGLU over ``p``'s w1/w3/w2 (ws* when ``shared``); ``proj(name,
    x)``, when given, computes the plain projections in their place."""
    s = "ws" if shared else "w"
    proj = proj or dense_proj(p)
    with jax.named_scope("mlp"):
        h = jax.nn.silu(proj(s + "1", x)) * proj(s + "3", x)
        h = constrain(h, "batch", None, "model")
        out = proj(s + "2", h)
        return constrain_resid(out)


def init_moe(cfg, key, dtype=jnp.float32):
    """The router over all ``n_experts``, and the experts this chip
    holds (``n_held`` where the configuration gives a share)."""
    e = cfg.moe
    d = cfg.d_model
    E = e.n_held or e.n_experts
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (d, e.n_experts), dtype=jnp.float32),
        "we1": dense_init(ks[1], (E, d, e.d_ff_expert),
                          fan_in=d, dtype=dtype),
        "we3": dense_init(ks[2], (E, d, e.d_ff_expert),
                          fan_in=d, dtype=dtype),
        "we2": dense_init(ks[3], (E, e.d_ff_expert, d),
                          fan_in=e.d_ff_expert, dtype=dtype),
    }
    if e.n_shared_experts:
        p.update(init_swiglu(d, e.n_shared_experts * e.d_ff_expert,
                             ks[4], dtype=dtype, prefix="s"))
    return p


def _gate(e, probs):
    """Greedy top-k of the gate probabilities: (weights, expert ids),
    the weights renormalised over the k where ``norm_topk`` says so,
    then scaled by ``routed_scale``."""
    topw, topi = jax.lax.top_k(probs, e.top_k)
    if e.norm_topk:
        topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)
    if e.routed_scale != 1.0:
        topw = topw * e.routed_scale
    return topw, topi


def _route_pack(cfg, router, xf, capacity_factor, exact_small=True):
    """Shared routing: top-k, aux loss, sort-pack into (E, C, d).
    Returns (xg, tok_s, w_s, keep, dest, C, aux)."""
    e = cfg.moe
    N, d = xf.shape
    K, E = e.top_k, e.n_experts
    logits = jnp.dot(xf.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    topw, topi = _gate(e, probs)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(topi[:, 0], E, dtype=jnp.float32), axis=0)
    aux = E * jnp.sum(me * ce)
    M = N * K
    eid = topi.reshape(M)
    tok = jnp.repeat(jnp.arange(N), K)
    w = topw.reshape(M)
    order = jnp.argsort(eid)
    eid_s, tok_s, w_s = eid[order], tok[order], w[order]
    counts = jnp.bincount(eid, length=E)
    offsets = jnp.cumsum(counts) - counts
    rank = jnp.arange(M) - offsets[eid_s]
    if exact_small and N <= 8192:
        C = N      # drop-free (decode determinism in the GSPMD path)
    else:
        C = max(8, math.ceil(K * N / E * capacity_factor))
    keep = rank < C
    dest = jnp.where(keep, eid_s * C + rank, E * C)
    xg = jnp.zeros((E * C + 1, d), xf.dtype).at[dest].set(xf[tok_s])
    return xg[:E * C].reshape(E, C, d), tok_s, w_s, keep, dest, C, aux


def _combine(xf_shape, y, tok_s, w_s, keep, dest, C, dtype):
    N, d = xf_shape
    yf = y.reshape(-1, d)
    gathered = yf[jnp.where(keep, dest, 0)] * keep[:, None]
    return jnp.zeros((N, d), dtype).at[tok_s].add(
        (w_s[:, None] * gathered).astype(dtype))


def moe_ffn_ep(cfg, p, x, capacity_factor: float = 1.25):
    """shard_map expert-parallel MoE (§Perf iter 2). x: (B,S,d) with the
    sequence dim sharded over the model axis inside the map."""
    env = current_axis_env()
    mesh = env.mesh
    m = env.model
    e = cfg.moe
    B, S, d = x.shape
    n = mesh.shape[m]
    import numpy as _np
    bsz = int(_np.prod([mesh.shape[a] for a in env.batch])) \
        if env.batch else 1
    bspec = (env.batch if len(env.batch) > 1 else env.batch[0]) \
        if env.batch and B % bsz == 0 else None

    def local_fn(xl, router, we1, we3, we2):
        # xl: (B_loc, S/n, d); we*: (E/n, ...)
        Bl, Sl, _ = xl.shape
        xf = xl.reshape(Bl * Sl, d)
        # capacity-based even for small local N: the exchange volume is
        # E*C*d, so C must track the mean load, not the worst case
        xg, tok_s, w_s, keep, dest, C, aux = _route_pack(
            cfg, router, xf, 1.5, exact_small=False)
        # exchange: every shard sends expert-slice j to shard j
        xg = jax.lax.all_to_all(xg, m, split_axis=0, concat_axis=1,
                                tiled=True)            # (E/n, C*n, d)
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xg, we1)) * \
            jnp.einsum("ecd,edf->ecf", xg, we3)
        y = jnp.einsum("ecf,efd->ecd", h, we2)         # (E/n, C*n, d)
        y = jax.lax.all_to_all(y, m, split_axis=1, concat_axis=0,
                               tiled=True)             # (E, C, d)
        out = _combine((Bl * Sl, d), y, tok_s, w_s, keep, dest, C, xl.dtype)
        axes = tuple(env.batch) + (m,)
        aux = jax.lax.pmean(aux, axes)
        return out.reshape(Bl, Sl, d), aux

    from jax import shard_map
    mapped = shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(bspec, m, None), P(None, None),
                  P(m, None, None), P(m, None, None), P(m, None, None)),
        out_specs=(P(bspec, m, None), P()), check_vma=False)
    y, aux = mapped(x, p["router"], p["we1"], p["we3"], p["we2"])
    if e.n_shared_experts:
        y = y + swiglu(p, x, shared=True)
    return constrain_resid(y), aux


def _ep_applicable(cfg, x) -> bool:
    env = current_axis_env()
    if SHARDING_MODE == "baseline" or env.mesh is None or env.model is None:
        return False
    n = env.mesh.shape[env.model]
    return (cfg.moe.n_experts % n == 0 and x.shape[1] % n == 0
            and x.shape[1] > 1
            and not cfg.moe.holds_share)


def held_moe_ffn(cfg, p, x, proj=None):
    """The expert layer of one chip's share: x (B,S,d) -> (y, (pairs,
    touched)).

    The router keeps its published width: a softmax over all
    ``n_experts`` gate logits, in fp32 at HIGHEST precision, and the
    greedy top-k over all of them. Only the (token, expert) pairs whose
    expert is held here (ids ``first_held`` .. ``first_held + n_held -
    1``) contribute, each weighted by its gate weight; the shared
    experts run for every token. Nothing stands in for the absent
    experts or their exchange. Each held expert computes every token,
    and the combine gives the tokens not routed to it weight 0, so no
    pair is ever dropped, at any N (C = N). ``pairs`` counts the routed
    pairs that land on held experts and ``touched`` the held experts
    with at least one (int32 scalars). ``proj(name, x)`` computes the
    expert and shared projections (the decode step's weight stream;
    ``we*`` take (N, d) or (E, N, f) and give (E, N, ·))."""
    e = cfg.moe
    proj = proj or dense_proj(p)
    B, S, d = x.shape
    N = B * S
    E = e.n_held
    xf = x.reshape(N, d)
    with jax.named_scope("router"):
        logits = jnp.dot(xf.astype(jnp.float32),
                         p["router"].astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        topw, topi = _gate(e, jax.nn.softmax(logits, axis=-1))
        onehot = (topi - e.first_held)[..., None] == jnp.arange(E)
        w = jnp.sum(jnp.where(onehot, topw[..., None], 0.0), axis=1)
        pairs = jnp.sum(onehot, dtype=jnp.int32)
        touched = jnp.sum(jnp.any(onehot, axis=(0, 1)), dtype=jnp.int32)
    with jax.named_scope("experts"):
        h = jax.nn.silu(proj("we1", xf)) * proj("we3", xf)   # (E,N,f)
        y = proj("we2", h)                                     # (E,N,d)
    with jax.named_scope("router"):
        out = jnp.sum(y.astype(jnp.float32) * w.T[:, :, None], axis=0)
        out = out.astype(x.dtype).reshape(B, S, d)
    if e.n_shared_experts:
        out = out + swiglu(p, x, shared=True, proj=proj)
    return constrain_resid(out), (pairs, touched)


def moe_ffn(cfg, p, x, capacity_factor: float = 1.25):
    """Sort-based ragged MoE. x: (B,S,d) -> (y, aux_loss).

    Dispatches to the shard_map expert-parallel path when the ambient
    mesh allows it (see module docstring), to ``held_moe_ffn`` where the
    configuration gives this chip a part of the experts, else the
    GSPMD scatter path. A part has no load-balance term (aux 0): the
    balance is the whole layer's, across the chips that share it, so a
    model is trained with all its experts held.
    """
    if cfg.moe is not None and _ep_applicable(cfg, x):
        return moe_ffn_ep(cfg, p, x, capacity_factor)
    if cfg.moe.holds_share:
        y, _ = held_moe_ffn(cfg, p, x)
        return y, jnp.zeros((), jnp.float32)
    e = cfg.moe
    B, S, d = x.shape
    N = B * S
    K = e.top_k
    E = e.n_experts
    xf = x.reshape(N, d)

    logits = jnp.dot(xf.astype(jnp.float32),
                     p["router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)  # (N,E)
    probs = jax.nn.softmax(logits, axis=-1)
    topw, topi = _gate(e, probs)                           # (N,K)

    # aux load-balance loss (Switch-style)
    me = jnp.mean(probs, axis=0)                            # (E,)
    ce = jnp.mean(
        jax.nn.one_hot(topi[:, 0], E, dtype=jnp.float32), axis=0)
    aux = E * jnp.sum(me * ce)

    M = N * K
    eid = topi.reshape(M)
    tok = jnp.repeat(jnp.arange(N), K)
    w = topw.reshape(M)

    order = jnp.argsort(eid)                               # stable
    eid_s, tok_s, w_s = eid[order], tok[order], w[order]
    counts = jnp.bincount(eid, length=E)
    offsets = jnp.cumsum(counts) - counts
    rank = jnp.arange(M) - offsets[eid_s]

    if N <= 8192:
        C = N            # exact (drop-free): worst case all tokens 1 expert
    else:
        C = max(1, math.ceil(K * N / E * capacity_factor))
    keep = rank < C
    dest = jnp.where(keep, eid_s * C + rank, E * C)        # E*C = drop slot

    xg = jnp.zeros((E * C + 1, d), x.dtype).at[dest].set(xf[tok_s])
    xg = xg[:E * C].reshape(E, C, d)
    xg = constrain(xg, "model", None, None)

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xg, p["we1"])) * \
        jnp.einsum("ecd,edf->ecf", xg, p["we3"])
    y = jnp.einsum("ecf,efd->ecd", h, p["we2"])
    y = constrain(y, "model", None, None)

    yf = y.reshape(E * C, d)
    gathered = yf[jnp.where(keep, dest, 0)] * keep[:, None]
    out = jnp.zeros((N, d), x.dtype).at[tok_s].add(
        (w_s[:, None] * gathered).astype(x.dtype))

    out = out.reshape(B, S, d)
    if e.n_shared_experts:
        out = out + swiglu(p, x, shared=True)
    return constrain_resid(out), aux
