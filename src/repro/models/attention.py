"""Attention variants: GQA (optionally sliding-window ring cache), MLA
(DeepSeek-V2 latent attention), and cross-attention (VLM / enc-dec).

Every projection accepts an optional ``lora`` hook: a callable
``lora(name, x) -> delta`` used by the serving engine to add batched
heterogeneous-adapter deltas on the Q/K/V/O projections (the paper's
attach points). GQA's base projections go through a ``proj`` hook,
``proj(name, x) -> x @ p[name]`` unless the caller gives its own (the
decode step's weight stream, ``kernels/stream.py``).

GQA names its work for the device trace: ``proj`` (the q/k/v/o
matmuls and biases) and ``attention`` (rope, the KV write, attention
over the cache or flash prefill); the hook names its own ``lora``. Its
decode cache is stacked over layers, ``(L, B, S, Kv*hd)``, and written
and read in place (``kernels/decode_attention.py`` on the TPU).
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

import numpy as _np
from jax.sharding import PartitionSpec as P

from repro.kernels.decode_attention import decode_attention

from .common import (SHARDING_MODE, apply_rope, attend_cache, constrain,
                     constrain_resid, current_axis_env, dense_init,
                     dense_proj, flash_attention, rmsnorm, yarn_freqs,
                     yarn_mscale)


def _zero_lora(name, x):
    return 0.0


def run_flash(q, k, v, *, causal, q_positions, k_positions, window=0,
              scale=None, extra_qk=None):
    """Flash attention, head-parallel under shard_map when the mesh
    divides the head dims (§Perf: keeping the kv-chunk scan fully local
    stops the SPMD partitioner from resharding scores in the backward
    pass). Falls back to the plain (GSPMD) path otherwise."""
    env = current_axis_env()
    kw = dict(causal=causal, q_positions=q_positions,
              k_positions=k_positions, window=window, scale=scale,
              extra_qk=extra_qk)
    if SHARDING_MODE == "baseline" or env.mesh is None or env.model is None:
        return flash_attention(q, k, v, **kw)
    mesh, m = env.mesh, env.model
    n = mesh.shape[m]
    B, _, H, _ = q.shape
    Kv = k.shape[2]
    if H % n or Kv % n:
        return flash_attention(q, k, v, **kw)
    bsz = int(_np.prod([mesh.shape[a] for a in env.batch])) \
        if env.batch else 1
    bspec = (env.batch if len(env.batch) > 1 else env.batch[0]) \
        if env.batch and B % bsz == 0 else None
    hspec = P(bspec, None, m, None)

    if extra_qk is not None:
        q2, k2 = extra_qk

        def local(q, k, v, q2, k2):
            return flash_attention(q, k, v, **{**kw, "extra_qk": (q2, k2)})

        return jax.shard_map(local, mesh=mesh,
                             in_specs=(hspec, hspec, hspec, hspec,
                                       P(bspec, None, None)),
                             out_specs=hspec, check_vma=False)(q, k, v, q2, k2)

    def local(q, k, v):
        return flash_attention(q, k, v, **kw)

    return jax.shard_map(local, mesh=mesh, in_specs=(hspec, hspec, hspec),
                         out_specs=hspec, check_vma=False)(q, k, v)


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------


def init_gqa(cfg, key, dtype=jnp.float32):
    d, H, Kv = cfg.d_model, cfg.n_heads, cfg.n_kv_heads
    hd = cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, H * hd), dtype=dtype),
        "wk": dense_init(ks[1], (d, Kv * hd), dtype=dtype),
        "wv": dense_init(ks[2], (d, Kv * hd), dtype=dtype),
        "wo": dense_init(ks[3], (H * hd, d), dtype=dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), dtype)
        p["bk"] = jnp.zeros((Kv * hd,), dtype)
        p["bv"] = jnp.zeros((Kv * hd,), dtype)
    return p


def _qkv(cfg, p, x, positions, lora, proj, rope: bool = True):
    B, S, d = x.shape
    H, Kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    with jax.named_scope("proj"):
        q = proj("wq", x) + lora("q", x)
        k = proj("wk", x) + lora("k", x)
        v = proj("wv", x) + lora("v", x)
        if cfg.qkv_bias:
            q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    with jax.named_scope("attention"):
        q = q.reshape(B, S, H, hd)
        k = k.reshape(B, S, Kv, hd)
        v = v.reshape(B, S, Kv, hd)
        if rope:
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
        q = constrain(q, "batch", None, "model", None)
        k = constrain(k, "batch", None, "model", None)
        v = constrain(v, "batch", None, "model", None)
    return q, k, v


def _mesh_model_size() -> int:
    env = current_axis_env()
    if SHARDING_MODE == "baseline" or env.mesh is None or env.model is None:
        return 0
    return env.mesh.shape[env.model]


def _regroup_plan(H: int, Kv: int, n: int):
    """Find (rep, Gp) such that Kv*rep divides the n-way model axis and
    queries regroup into Kv*rep uniform groups of Gp = ceil(G/rep) —
    padding each sub-group with zero queries when rep does not divide G.
    Returns None when no plan exists (or none is needed)."""
    if n == 0 or Kv % n == 0:
        return None
    G = H // Kv
    rep = 1
    while rep <= G:
        if (Kv * rep) % n == 0:
            return rep, -(-G // rep)
        rep += 1
    return None


def _pad_regroup_q(q, Kv: int, rep: int, Gp: int):
    """q: (B,S,H,hd) with H = Kv*G -> (B,S,Kv*rep*Gp,hd): each kv head's
    G queries are split across its `rep` duplicates in Gp-sized
    sub-groups, zero-padded to uniform size (zero queries attend
    uniformly; their outputs are sliced away by _unpad_o)."""
    B, S, H, hd = q.shape
    G = H // Kv
    qr = q.reshape(B, S, Kv, G, hd)
    qr = jnp.pad(qr, ((0, 0), (0, 0), (0, 0), (0, rep * Gp - G), (0, 0)))
    return qr.reshape(B, S, Kv * rep * Gp, hd)


def _unpad_o(o, Kv: int, G: int, rep: int, Gp: int):
    B, S = o.shape[:2]
    hd = o.shape[-1]
    orr = o.reshape(B, S, Kv, rep * Gp, hd)
    return orr[:, :, :, :G].reshape(B, S, Kv * G, hd)


def gqa_full(cfg, p, x, positions, *, causal=True, window=0,
             lora: Optional[Callable] = None):
    """Full-sequence attention. Returns (out, (k, v)) for cache seeding."""
    lora = lora or _zero_lora
    q, k, v = _qkv(cfg, p, x, positions, lora, dense_proj(p))
    H, Kv = cfg.n_heads, cfg.n_kv_heads
    plan = _regroup_plan(H, Kv, _mesh_model_size())
    B, S = x.shape[:2]
    with jax.named_scope("attention"):
        if plan is not None:
            # §Perf iter 4: duplicate kv heads (+ zero-pad query groups)
            # so the head dims divide the mesh and the shard_map flash
            # path engages — an identity transform, validated in
            # test_models_features.test_kv_regroup_identity.
            rep, Gp = plan
            qf = _pad_regroup_q(q, Kv, rep, Gp)
            kf = jnp.repeat(k, rep, axis=2)
            vf = jnp.repeat(v, rep, axis=2)
            o = run_flash(qf, kf, vf, causal=causal, q_positions=positions,
                          k_positions=positions, window=window,
                          scale=1.0 / (cfg.resolved_head_dim ** 0.5))
            o = _unpad_o(o, Kv, H // Kv, rep, Gp)
        else:
            o = run_flash(q, k, v, causal=causal, q_positions=positions,
                          k_positions=positions, window=window)
        o = o.reshape(B, S, -1)
    with jax.named_scope("proj"):
        out = o @ p["wo"] + lora("o", o)
    return constrain_resid(out), (k, v)


def gqa_decode(cfg, p, x, k_cache, v_cache, pos, *, layer=0, window=0,
               lora: Optional[Callable] = None,
               proj: Optional[Callable] = None, kernel: bool = False):
    """Single-token decode. x: (B,1,d); caches: the stacked
    (L,B,S,Kv*hd) caches, this layer's being ``layer``; pos: (B,) int32
    current position of the new token per row (S or more: a free slot,
    whose write drops). The new token's k/v line is written in place at
    (layer, row, pos) (ring-indexed when window>0), then the layer is
    read: with ``kernel``, its live blocks in place by
    ``decode_attention``, else the whole layer by ``attend_cache``.
    Returns (out, (k_cache, v_cache))."""
    lora = lora or _zero_lora
    proj = proj or dense_proj(p)
    B = x.shape[0]
    S = k_cache.shape[2]
    Kv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    q, k, v = _qkv(cfg, p, x, pos[:, None], lora, proj)
    with jax.named_scope("attention"):
        if SHARDING_MODE != "baseline":
            # opt (§Perf iter 1): the cache is sequence-sharded over the
            # model axis (context-parallel decode); the new token's k/v
            # is tiny — replicate it rather than asking for a kv-head
            # layout the mesh cannot divide (avoids the (8,2)<->(16,1)
            # reshard storm).
            k = constrain(k, "batch", None, None, None)
            v = constrain(v, "batch", None, None, None)
        write_idx = pos % S if window else pos
        bidx = jnp.arange(B)
        k_cache = k_cache.at[layer, bidx, write_idx].set(
            k.reshape(B, -1).astype(k_cache.dtype))
        v_cache = v_cache.at[layer, bidx, write_idx].set(
            v.reshape(B, -1).astype(v_cache.dtype))
        if kernel:
            live = jnp.where(pos < S, pos + 1, 0)
            o = decode_attention(q[:, 0], k_cache, v_cache, layer, live)
        else:
            slots = jnp.arange(S)[None, :]
            valid = slots <= jnp.minimum(pos, S - 1)[:, None]
            o = attend_cache(q, k_cache[layer].reshape(B, S, Kv, hd),
                             v_cache[layer].reshape(B, S, Kv, hd), valid)
        o = o.reshape(B, 1, -1)
    with jax.named_scope("proj"):
        out = proj("wo", o) + lora("o", o)
    return constrain_resid(out), (k_cache, v_cache)


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2): compressed KV cache of (c_kv, k_rope). LoRA targets
# the release's projections: q (q_proj), kv_a (kv_a_proj_with_mqa: the
# latent and the rope key), kv_b (kv_b_proj: W_UK and W_UV, per head
# [k_nope | v]) and o.
# ---------------------------------------------------------------------------


def init_mla(cfg, key, dtype=jnp.float32):
    m = cfg.mla
    d, H = cfg.d_model, cfg.n_heads
    qd = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = jax.random.split(key, 5)
    return {
        "wq": dense_init(ks[0], (d, H * qd), dtype=dtype),
        "w_dkv": dense_init(ks[1], (d, m.kv_lora_rank + m.qk_rope_head_dim),
                            dtype=dtype),
        "ln_kv": jnp.ones((m.kv_lora_rank,), dtype),
        "w_uk": dense_init(ks[2], (m.kv_lora_rank, H * m.qk_nope_head_dim),
                           dtype=dtype),
        "w_uv": dense_init(ks[3], (m.kv_lora_rank, H * m.v_head_dim),
                           dtype=dtype),
        "wo": dense_init(ks[4], (H * m.v_head_dim, d), dtype=dtype),
    }


def _mla_rope(cfg, x, positions):
    """The rope dims rotated as DeepSeek-V2 rotates them: YaRN's
    frequencies where the configuration scales them, each even dim
    against the odd dim after it."""
    m = cfg.mla
    freqs = None
    if cfg.rope_scaling is not None:
        freqs = yarn_freqs(m.qk_rope_head_dim, cfg.rope_theta,
                           cfg.rope_scaling)
    return apply_rope(x, positions, cfg.rope_theta, freqs=freqs,
                      interleaved=True)


def mla_scale(cfg) -> float:
    """Softmax scale: 1/sqrt(q head dim), times YaRN's mscale squared."""
    m = cfg.mla
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    s = cfg.rope_scaling
    if s is not None and s.mscale_all_dim:
        scale *= yarn_mscale(s.factor, s.mscale_all_dim) ** 2
    return scale


def _mla_proj(cfg, p, x, positions, lora, proj):
    """q (B,S,H,qd) with its rope dims rotated, and the new latent c
    (B,S,kv_lora) after ``kv_a_layernorm`` and rope key kr (B,S,1,rope).
    LoRA targets ``q`` and ``kv_a`` (the latent and rope key
    projection)."""
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.n_heads
    qd = m.qk_nope_head_dim + m.qk_rope_head_dim
    with jax.named_scope("proj"):
        q = proj("wq", x) + lora("q", x)
        dkv = proj("w_dkv", x) + lora("kv_a", x)
    with jax.named_scope("attention"):
        qn, qr = jnp.split(q.reshape(B, S, H, qd), [m.qk_nope_head_dim],
                           axis=-1)
        q = jnp.concatenate([qn, _mla_rope(cfg, qr, positions)], axis=-1)
        c, kr = jnp.split(dkv, [m.kv_lora_rank], axis=-1)
        c = rmsnorm(c, p["ln_kv"], cfg.rmsnorm_eps)
        kr = _mla_rope(cfg, kr.reshape(B, S, 1, m.qk_rope_head_dim),
                       positions)
    return q, c, kr


def _mla_expand(cfg, p, c, lora):
    """Expand a latent (B,S,kv_lora) into per-head K_nope and V; the
    ``kv_b`` LoRA delta is laid out per head as [k_nope | v]."""
    m = cfg.mla
    B, S, _ = c.shape
    H = cfg.n_heads
    kn = (c @ p["w_uk"]).reshape(B, S, H, m.qk_nope_head_dim)
    v = (c @ p["w_uv"]).reshape(B, S, H, m.v_head_dim)
    delta = lora("kv_b", c)
    if not isinstance(delta, float):
        dk, dv = jnp.split(
            delta.reshape(B, S, H, m.qk_nope_head_dim + m.v_head_dim),
            [m.qk_nope_head_dim], axis=-1)
        kn, v = kn + dk, v + dv
    return kn, v


def _lora_factors(lora, name):
    """The per-row (A, B, row mask) factors of target ``name`` that a
    LoRA hook holds (``lora/batched.py``); none without a bank."""
    factors = getattr(lora, "factors", None)
    return factors(name) if factors is not None else []


def mla_full(cfg, p, x, positions, *, causal=True, window=0, lora=None):
    lora = lora or _zero_lora
    m = cfg.mla
    B, S, _ = x.shape
    q, c, kr = _mla_proj(cfg, p, x, positions, lora, dense_proj(p))
    with jax.named_scope("attention"):
        kn, v = _mla_expand(cfg, p, c, lora)
        # score = q_nope.k_nope + q_rope.k_rope computed as two einsums —
        # the shared rope key never gets broadcast+concat'd into a
        # per-head K (§Perf iter 2d: that concat reshards scores inside
        # the kv scan)
        qn, qr = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
        o = run_flash(qn, kn, v, causal=causal, q_positions=positions,
                      k_positions=positions, window=window,
                      scale=mla_scale(cfg), extra_qk=(qr, kr[:, :, 0, :]))
        o = o.reshape(B, S, -1)
    with jax.named_scope("proj"):
        out = o @ p["wo"] + lora("o", o)
    return constrain_resid(out), (c, kr[:, :, 0, :])


def _absorbed(cfg, p, qn, qr, c_cache, kr_cache, valid, lora):
    """Attention of one query per row over the latent cache, never
    expanding it: score = (q_nope W_UK^T) . c + q_rope . k_rope and
    out = (p . c) W_UV per head. A ``kv_b`` adapter adds, per query,
    (q_nope B_k^T) A^T to the absorbed query and (o_lat A) B_v to the
    output: two low-rank terms per head, nothing per cached token.

    Every product is fp32 at HIGHEST precision: a few rows against
    per-layer weights and the cache, so the passes cost nothing beside
    the bytes, and the fp32 operands are read in place (a default-
    precision dot would make bf16 copies of W_UK, W_UV and the bank's
    kv_b A, hoisted out of the decode loop as whole stacks)."""
    m = cfg.mla
    B, H = qn.shape[:2]
    R, n, dv = m.kv_lora_rank, m.qk_nope_head_dim, m.v_head_dim
    f32 = jnp.float32

    def einsum(spec, a, b):
        return jnp.einsum(spec, a.astype(f32), b.astype(f32),
                          precision=jax.lax.Precision.HIGHEST)

    factors = []
    for A, Bm, mask in _lora_factors(lora, "kv_b"):
        with jax.named_scope("lora"):
            Bm = Bm.reshape(B, -1, H, n + dv)
            factors.append((A, Bm[..., :n], Bm[..., n:], mask))
    q_lat = einsum("bhn,rhn->bhr", qn, p["w_uk"].reshape(R, H, n))
    for A, Bk, _, mask in factors:
        with jax.named_scope("lora"):
            t = einsum("bhr,bkhr->bhk", qn, Bk)
            q_lat = q_lat + _masked(einsum("bhk,bck->bhc", t, A), mask)
    s = einsum("bhr,bsr->bhs", q_lat, c_cache)
    s += einsum("bhr,bsr->bhs", qr, kr_cache)
    s = jnp.where(valid[:, None, :], s * mla_scale(cfg), -1e30)
    pr = jax.nn.softmax(s, axis=-1)
    o_lat = einsum("bhs,bsr->bhr", pr, c_cache)
    o = einsum("bhr,rhv->bhv", o_lat, p["w_uv"].reshape(R, H, dv))
    for A, _, Bv, mask in factors:
        with jax.named_scope("lora"):
            u = einsum("bhc,bck->bhk", o_lat, A)
            o = o + _masked(einsum("bhk,bkhv->bhv", u, Bv), mask)
    return o


def _masked(y, mask):
    return y if mask is None else jnp.where(mask[:, None, None], y, 0.0)


def mla_decode(cfg, p, x, c_cache, kr_cache, pos, *, window=0, lora=None,
               absorbed: bool = False, proj: Optional[Callable] = None):
    """c_cache: (B,S,kv_lora); kr_cache: (B,S,rope_dim).

    ``absorbed=False`` is the paper-faithful naive path: expand the full
    cached latent into per-head K/V each step. ``absorbed=True`` applies
    the W_UK/W_UV absorption identity (``_absorbed``): the latent cache
    is read once and never expanded. ``proj`` computes the q, latent and
    output projections (the decode step's weight stream)."""
    lora = lora or _zero_lora
    proj = proj or dense_proj(p)
    m = cfg.mla
    B = x.shape[0]
    S = c_cache.shape[1]
    H = cfg.n_heads
    q, c_t, kr_t = _mla_proj(cfg, p, x, pos[:, None], lora, proj)
    with jax.named_scope("attention"):
        write_idx = pos % S if window else pos
        bidx = jnp.arange(B)
        c_cache = c_cache.at[bidx, write_idx].set(c_t[:, 0])
        kr_cache = kr_cache.at[bidx, write_idx].set(kr_t[:, 0, 0])
        slots = jnp.arange(S)[None, :]
        valid = slots <= jnp.minimum(pos, S - 1)[:, None]
        if absorbed:
            qn, qr = jnp.split(q[:, 0], [m.qk_nope_head_dim], axis=-1)
            o = _absorbed(cfg, p, qn, qr, c_cache, kr_cache, valid, lora)
            o = o.reshape(B, 1, H * m.v_head_dim).astype(x.dtype)
        else:
            kn, v = _mla_expand(cfg, p, c_cache, lora)     # (B,S,H,*)
            k = jnp.concatenate(
                [kn, jnp.broadcast_to(kr_cache[:, :, None, :],
                                      (B, S, H, m.qk_rope_head_dim))],
                axis=-1)
            o = attend_cache(q, k, v, valid, scale=mla_scale(cfg))
            o = o.reshape(B, 1, -1)
    with jax.named_scope("proj"):
        out = proj("wo", o) + lora("o", o)
    return constrain_resid(out), (c_cache, kr_cache)


# ---------------------------------------------------------------------------
# Cross attention (VLM image layers / enc-dec decoder)
# ---------------------------------------------------------------------------


def init_cross_attn(cfg, key, dtype=jnp.float32):
    d, H, Kv = cfg.d_model, cfg.n_heads, cfg.n_kv_heads
    hd = cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], (d, H * hd), dtype=dtype),
        "wk": dense_init(ks[1], (d, Kv * hd), dtype=dtype),
        "wv": dense_init(ks[2], (d, Kv * hd), dtype=dtype),
        "wo": dense_init(ks[3], (H * hd, d), dtype=dtype),
    }


def cross_kv(cfg, p, memory):
    """Precompute cross-attn K/V from memory (B,M,d). Cached once."""
    B, M, _ = memory.shape
    Kv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    k = (memory @ p["wk"]).reshape(B, M, Kv, hd)
    v = (memory @ p["wv"]).reshape(B, M, Kv, hd)
    return k, v


def cross_attend(cfg, p, x, k, v, lora=None):
    """x: (B,S,d) queries; k/v: (B,M,Kv,hd) precomputed. Non-causal."""
    lora = lora or _zero_lora
    B, S, _ = x.shape
    H, hd = cfg.n_heads, cfg.resolved_head_dim
    q = (x @ p["wq"] + lora("q", x)).reshape(B, S, H, hd)
    q = constrain(q, "batch", None, "model", None)
    if S == 1:
        M = k.shape[1]
        valid = jnp.ones((B, M), dtype=bool)
        o = attend_cache(q, k, v, valid)
    else:
        M = k.shape[1]
        o = flash_attention(q, k, v, causal=False,
                            q_positions=jnp.arange(S),
                            k_positions=jnp.arange(M))
    o = o.reshape(B, S, -1)
    out = o @ p["wo"] + lora("o", o)
    return constrain_resid(out)
