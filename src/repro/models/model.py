"""Model assembly: init / forward (train) / prefill / decode for every
assigned architecture family.

All stacks run as ``lax.scan`` over stacked layer params (with optional
per-layer ``jax.checkpoint`` remat for training) so HLO size stays bounded
at 64–100 layers. Caches are plain dict pytrees (stacked along a leading
layer axis) so they thread through jit/pjit and can be donated.

Cache dict keys (present depending on family):
  pos    : (B,) int32 — tokens currently in the cache per row
  k, v   : (L_attn, B, S, Kv*hd) self-attention KV, a token one
           lane-dense line of every KV head side by side
  c, kr  : (L, B, S, kv_lora) / (L, B, S, rope) MLA compressed cache
  expert_counts : {"pairs", "touched": (L_moe,), "steps": ()} int32,
           what the decode steps' held-expert layers routed (no batch
           axis; the engine's merge keeps its own)
  xk, xv : (L_cross, B, M, Kv, hd) cross-attn KV (computed at prefill)
  ssm    : (L, B, H, hd, N) mamba2 state
  wkv/x_tm/x_cm : RWKV6 state (stacked over layers)
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro import kernels
from repro.kernels.stream import stream_matmul
from repro.lora.batched import make_lora_cb

from .attention import (cross_attend, cross_kv, gqa_decode, gqa_full,
                        init_cross_attn, init_gqa, init_mla, mla_decode,
                        mla_full)
from .common import (chunked_cross_entropy, constrain, constrain_resid,
                     current_axis_env, dense_init, rmsnorm)
from .ffn import held_moe_ffn, init_moe, init_swiglu, moe_ffn, swiglu
from .ssm import (init_mamba2, init_rwkv6, mamba2_full, mamba2_state,
                  mamba2_step, rwkv6_channel_mix, rwkv6_state, rwkv6_time_mix,
                  rwkv_dims, mamba_dims)

# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _init_attn(cfg, key, dtype):
    return init_mla(cfg, key, dtype) if cfg.mla else init_gqa(cfg, key, dtype)


def _init_dense_block(cfg, key, dtype, leading=False):
    """One block; a ``leading`` dense layer of a MoE model has a SwiGLU
    of ``dense_d_ff`` in place of the experts."""
    d = cfg.d_model
    k1, k2 = jax.random.split(key)
    p = {"ln1": jnp.ones((d,), dtype), "ln2": jnp.ones((d,), dtype),
         "attn": _init_attn(cfg, k1, dtype)}
    if leading:
        p["ffn"] = init_swiglu(d, cfg.dense_d_ff, k2, dtype)
    elif cfg.moe is not None:
        p["ffn"] = init_moe(cfg, k2, dtype)
    else:
        p["ffn"] = init_swiglu(d, cfg.d_ff, k2, dtype)
    return p


def _init_cross_block(cfg, key, dtype):
    d = cfg.d_model
    k1, k2 = jax.random.split(key)
    return {"ln1": jnp.ones((d,), dtype), "ln2": jnp.ones((d,), dtype),
            "attn": init_cross_attn(cfg, k1, dtype),
            "ffn": init_swiglu(d, cfg.d_ff, k2, dtype),
            "gate_attn": jnp.zeros((1,), dtype),
            "gate_ffn": jnp.zeros((1,), dtype)}


def _init_encdec_dec_block(cfg, key, dtype):
    d = cfg.d_model
    k1, k2, k3 = jax.random.split(key, 3)
    return {"ln1": jnp.ones((d,), dtype), "lnc": jnp.ones((d,), dtype),
            "ln2": jnp.ones((d,), dtype),
            "attn": _init_attn(cfg, k1, dtype),
            "cross": init_cross_attn(cfg, k2, dtype),
            "ffn": init_swiglu(d, cfg.d_ff, k3, dtype)}


def _init_mamba_block(cfg, key, dtype):
    return init_mamba2(cfg, key, dtype)


def _stacked(init_fn, n, key, *args):
    keys = jax.random.split(key, n)
    return jax.vmap(lambda k: init_fn(k, *args))(keys)


def init_params(cfg, key, dtype=jnp.float32):
    d, V = cfg.d_model, cfg.vocab_size
    key, ke, kh, kb = jax.random.split(key, 4)
    p = {"embed": dense_init(ke, (V, d), fan_in=d, dtype=dtype),
         "ln_f": jnp.ones((d,), dtype)}
    if not cfg.tie_embeddings:
        p["lm_head"] = dense_init(kh, (d, V), dtype=dtype)

    fam = cfg.family
    if fam in ("dense", "moe"):
        nd = cfg.n_dense_layers
        if nd:
            kb, kd = jax.random.split(kb)
            p["dense_blocks"] = _stacked(
                lambda k: _init_dense_block(cfg, k, dtype, leading=True),
                nd, kd)
        p["blocks"] = _stacked(lambda k: _init_dense_block(cfg, k, dtype),
                               cfg.n_layers - nd, kb)
    elif fam == "vlm":
        n_cross = cfg.n_layers // cfg.cross_attn_every
        n_self = cfg.n_layers - n_cross
        k1, k2 = jax.random.split(kb)
        p["self_blocks"] = _stacked(
            lambda k: _init_dense_block(cfg, k, dtype), n_self, k1)
        p["cross_blocks"] = _stacked(
            lambda k: _init_cross_block(cfg, k, dtype), n_cross, k2)
    elif fam == "audio":
        k1, k2 = jax.random.split(kb)
        p["enc_blocks"] = _stacked(
            lambda k: _init_dense_block(cfg, k, dtype),
            cfg.encoder.n_layers, k1)
        p["enc_ln_f"] = jnp.ones((d,), dtype)
        p["dec_blocks"] = _stacked(
            lambda k: _init_encdec_dec_block(cfg, k, dtype),
            cfg.n_layers, k2)
    elif fam == "hybrid":
        k1, k2 = jax.random.split(kb)
        p["mamba_blocks"] = _stacked(
            lambda k: _init_mamba_block(cfg, k, dtype), cfg.n_layers, k1)
        p["shared_attn"] = _init_dense_block(cfg, k2, dtype)
    elif fam == "ssm":
        p["blocks"] = _stacked(lambda k: init_rwkv6(cfg, k, dtype),
                               cfg.n_layers, kb)
    else:
        raise ValueError(f"unknown family {fam}")
    return p


def lm_head(cfg, params):
    return params["embed"].T if cfg.tie_embeddings else params["lm_head"]


def n_attn_applications(cfg) -> int:
    """Number of self-attention cache entries (stacked leading dim)."""
    if cfg.family == "hybrid":
        return -(-cfg.n_layers // cfg.attn_every)
    if cfg.family == "ssm":
        return 0
    if cfg.family == "vlm":
        return cfg.n_layers - cfg.n_layers // cfg.cross_attn_every
    return cfg.n_layers


def n_cross_applications(cfg) -> int:
    if cfg.family == "vlm":
        return cfg.n_layers // cfg.cross_attn_every
    if cfg.family == "audio":
        return cfg.n_layers
    return 0


# ---------------------------------------------------------------------------
# Blocks (full-sequence and decode forms)
# ---------------------------------------------------------------------------


def _dense_block_full(cfg, bp, x, positions, window, lora):
    attn_fn = mla_full if cfg.mla else gqa_full
    h, kv = attn_fn(cfg, bp["attn"], rmsnorm(x, bp["ln1"], cfg.rmsnorm_eps),
                    positions, window=window, lora=lora)
    x = x + h
    xn = rmsnorm(x, bp["ln2"], cfg.rmsnorm_eps)
    if "router" in bp["ffn"]:
        f, aux = moe_ffn(cfg, bp["ffn"], xn)
    else:
        f, aux = swiglu(bp["ffn"], xn), jnp.zeros((), jnp.float32)
    return x + f, kv, aux


def _dense_block_decode(cfg, bp, x, kc, vc, pos, window, lora,
                        mla_absorbed=False, proj=None, layer=0,
                        kernel=False):
    """``proj``, when given, computes the attention, SwiGLU and expert
    projections whose weights ``bp`` then leaves out (see
    ``decode_step``). GQA's ``kc``/``vc`` are the stacked caches, this
    block being their ``layer`` (``gqa_decode``); MLA's are the layer's
    own. Returns (x, kc, vc, stats): ``stats`` the held expert layer's
    (pairs, touched), else None."""
    if cfg.mla:
        h, (kc, vc) = mla_decode(cfg, bp["attn"],
                                 rmsnorm(x, bp["ln1"], cfg.rmsnorm_eps),
                                 kc, vc, pos, window=window, lora=lora,
                                 absorbed=mla_absorbed, proj=proj)
    else:
        h, (kc, vc) = gqa_decode(cfg, bp["attn"],
                                 rmsnorm(x, bp["ln1"], cfg.rmsnorm_eps),
                                 kc, vc, pos, layer=layer, window=window,
                                 lora=lora, proj=proj, kernel=kernel)
    x = x + h
    xn = rmsnorm(x, bp["ln2"], cfg.rmsnorm_eps)
    stats = None
    if "router" not in bp["ffn"]:
        f = swiglu(bp["ffn"], xn, proj=proj)
    elif cfg.moe.holds_share:
        f, stats = held_moe_ffn(cfg, bp["ffn"], xn, proj=proj)
    else:
        f, _ = moe_ffn(cfg, bp["ffn"], xn)
    return x + f, kc, vc, stats


def _cross_block(cfg, bp, x, kc, vc, lora):
    g_a = jnp.tanh(bp["gate_attn"])
    g_f = jnp.tanh(bp["gate_ffn"])
    h = cross_attend(cfg, bp["attn"], rmsnorm(x, bp["ln1"], cfg.rmsnorm_eps),
                     kc, vc, lora)
    x = x + g_a * h
    x = x + g_f * swiglu(bp["ffn"], rmsnorm(x, bp["ln2"], cfg.rmsnorm_eps))
    return x


def _rwkv_block(cfg, bp, x, st, lora):
    h, st_tm = rwkv6_time_mix(cfg, bp, rmsnorm(x, bp["ln1"], cfg.rmsnorm_eps),
                              st, lora)
    x = x + h
    h2, st_cm = rwkv6_channel_mix(
        cfg, bp, rmsnorm(x, bp["ln2"], cfg.rmsnorm_eps), st)
    return x + h2, {**st_tm, **st_cm}


# ---------------------------------------------------------------------------
# Full-sequence runners (train forward / prefill). Return (h, caches, aux).
# ---------------------------------------------------------------------------


def _layer_slice(tree, i):
    """Layer ``i`` of every stacked leaf of ``tree``."""
    return jax.tree.map(
        lambda t: jax.lax.dynamic_index_in_dim(t, i, 0, keepdims=False),
        tree)


def _bank_slice(bank, i=None):
    if bank is None:
        return None
    return bank if i is None else jax.tree.map(lambda t: t[i], bank)


def _run_dense_full(cfg, params, x, positions, *, window, bank, lora_idx,
                    remat, collect, lora_kernel="einsum"):
    has_bank = bank is not None

    def layer(x, aux, bp, bk):
        lora = make_lora_cb(bk, lora_idx, kernel=lora_kernel) \
            if bk is not None else None
        x, kv, a = _dense_block_full(cfg, bp, x, positions, window, lora)
        return x, aux + a, kv

    if not cfg.n_dense_layers:
        def body(carry, inp):
            x, aux = carry
            bp, bk = inp if has_bank else (inp, None)
            x, aux, kv = layer(x, aux, bp, bk)
            return (x, aux), (kv if collect else 0)

        body_fn = jax.checkpoint(body) if remat else body
        xs = (params["blocks"], bank) if has_bank else params["blocks"]
        (x, aux), kvs = jax.lax.scan(body_fn,
                                     (x, jnp.zeros((), jnp.float32)), xs)
        return x, kvs, aux

    # the leading dense stack, then the MoE stack: the bank spans both,
    # so each layer takes its slice by index (never a copy of a part)
    def stack_body(carry, bp):
        x, aux, i = carry
        bk = _layer_slice(bank, i) if has_bank else None
        x, aux, kv = layer(x, aux, bp, bk)
        return (x, aux, i + 1), (kv if collect else 0)

    body_fn = jax.checkpoint(stack_body) if remat else stack_body
    carry = (x, jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32))
    carry, kv_dense = jax.lax.scan(body_fn, carry, params["dense_blocks"])
    (x, aux, _), kvs = jax.lax.scan(body_fn, carry, params["blocks"])
    if collect:
        kvs = jax.tree.map(lambda a, b: jnp.concatenate([a, b]),
                           kv_dense, kvs)
    return x, kvs, aux


def _run_vlm_full(cfg, params, x, positions, *, window, frontend, bank,
                  lora_idx, remat, collect, lora_kernel="einsum"):
    n_cross = cfg.n_layers // cfg.cross_attn_every
    per = cfg.cross_attn_every - 1          # self layers per period
    sb = jax.tree.map(
        lambda t: t.reshape((n_cross, per) + t.shape[1:]),
        params["self_blocks"])
    xkv = jax.vmap(lambda bp: cross_kv(cfg, bp["attn"], frontend))(
        params["cross_blocks"])              # (n_cross, B, M, Kv, hd) x2

    def self_body(carry, bp):
        x, aux = carry
        x, kv, a = _dense_block_full(cfg, bp, x, positions, window,
                                     make_lora_cb(None, lora_idx))
        return (x, aux + a), (kv if collect else 0)

    self_body_fn = jax.checkpoint(self_body) if remat else self_body

    def period_body(carry, inp):
        blocks_i, cross_bp, xk, xv = inp
        carry, kvs = jax.lax.scan(self_body_fn, carry, blocks_i)
        x, aux = carry
        x = _cross_block(cfg, cross_bp, x, xk, xv, None)
        return (x, aux), kvs

    (x, aux), kvs = jax.lax.scan(
        period_body, (x, jnp.zeros((), jnp.float32)),
        (sb, params["cross_blocks"], xkv[0], xkv[1]))
    if collect:
        kvs = jax.tree.map(
            lambda t: t.reshape((-1,) + t.shape[2:]), kvs)
    return x, (kvs, xkv), aux


def _run_audio_encoder(cfg, params, frames):
    pos = jnp.arange(frames.shape[1])

    def body(x, bp):
        x, _, _ = _dense_block_full(cfg, bp, x, pos, 0, None)
        return x, 0

    # encoder self-attn is bidirectional: reuse dense block with causal off
    def enc_block(x, bp):
        h, _ = gqa_full(cfg, bp["attn"],
                        rmsnorm(x, bp["ln1"], cfg.rmsnorm_eps), pos,
                        causal=False)
        x = x + h
        x = x + swiglu(bp["ffn"], rmsnorm(x, bp["ln2"], cfg.rmsnorm_eps))
        return x, 0

    x, _ = jax.lax.scan(enc_block, frames, params["enc_blocks"])
    return rmsnorm(x, params["enc_ln_f"], cfg.rmsnorm_eps)


def _run_audio_full(cfg, params, x, positions, *, window, frontend, bank,
                    lora_idx, remat, collect, lora_kernel="einsum"):
    memory = _run_audio_encoder(cfg, params, frontend)
    xkv = jax.vmap(lambda bp: cross_kv(cfg, bp["cross"], memory))(
        params["dec_blocks"])

    has_bank = bank is not None

    def body(carry, inp):
        x, aux = carry
        if has_bank:
            bp, xk, xv, bk = inp
        else:
            (bp, xk, xv), bk = inp, None
        lora = make_lora_cb(bk, lora_idx, kernel=lora_kernel) \
            if bk is not None else None
        h, kv = gqa_full(cfg, bp["attn"],
                         rmsnorm(x, bp["ln1"], cfg.rmsnorm_eps),
                         positions, window=window, lora=lora)
        x = x + h
        x = x + cross_attend(cfg, bp["cross"],
                             rmsnorm(x, bp["lnc"], cfg.rmsnorm_eps), xk, xv)
        x = x + swiglu(bp["ffn"], rmsnorm(x, bp["ln2"], cfg.rmsnorm_eps))
        return (x, aux), (kv if collect else 0)

    body_fn = jax.checkpoint(body) if remat else body
    xs = (params["dec_blocks"], xkv[0], xkv[1], bank) if has_bank \
        else (params["dec_blocks"], xkv[0], xkv[1])
    (x, aux), kvs = jax.lax.scan(body_fn, (x, jnp.zeros((), jnp.float32)),
                                 xs)
    return x, (kvs, xkv), aux


def _hybrid_segments(cfg):
    """[(n_mamba_layers, start_idx)] per shared-attn application."""
    segs = []
    start = 0
    while start < cfg.n_layers:
        size = min(cfg.attn_every, cfg.n_layers - start)
        segs.append((start, size))
        start += size
    return segs


def _run_hybrid_full(cfg, params, x, positions, *, window, bank, lora_idx,
                     remat, collect, lora_kernel="einsum"):
    B = x.shape[0]
    aux = jnp.zeros((), jnp.float32)
    kv_list = []
    state_list = []
    lora = make_lora_cb(_bank_slice(bank, 0) if bank is not None else None,
                        lora_idx, kernel=lora_kernel)

    def mamba_body(x, inp):
        bp, st = inp
        out, st2 = mamba2_full(cfg, bp, rmsnorm(x, bp["ln"], cfg.rmsnorm_eps),
                               st)
        return x + out, st2

    mamba_body_fn = jax.checkpoint(mamba_body) if remat else mamba_body

    for (start, size) in _hybrid_segments(cfg):
        x, kv, a = _dense_block_full(cfg, params["shared_attn"], x,
                                     positions, window, lora)
        aux = aux + a
        kv_list.append(kv)
        sub = jax.tree.map(
            lambda t: jax.lax.slice_in_dim(t, start, start + size),
            params["mamba_blocks"])
        st0 = jnp.zeros((size,) + mamba2_state(cfg, B).shape)
        x, sts = jax.lax.scan(mamba_body_fn, x, (sub, st0))
        state_list.append(sts)

    kvs = jax.tree.map(lambda *t: jnp.stack(t), *kv_list) if collect else None
    states = jnp.concatenate(state_list, axis=0)
    return x, (kvs, states), aux


def _run_rwkv_full(cfg, params, x, *, bank, lora_idx, remat, collect,
                   lora_kernel="einsum"):
    B = x.shape[0]
    L = cfg.n_layers
    st0 = jax.tree.map(lambda t: jnp.broadcast_to(t, (L,) + t.shape),
                       rwkv6_state(cfg, B, x.dtype))

    def body(x, inp):
        bp, st, bk = inp
        lora = make_lora_cb(bk, lora_idx, kernel=lora_kernel) \
            if bk is not None else None
        x, st2 = _rwkv_block(cfg, bp, x, st, lora)
        return x, st2

    body_fn = jax.checkpoint(body) if remat else body
    if bank is not None:
        xs = (params["blocks"], st0, bank)
    else:
        xs = (params["blocks"], st0)

    def body2(x, inp):
        if bank is not None:
            bp, st, bk = inp
        else:
            (bp, st), bk = inp, None
        return body_fn(x, (bp, st, bk))

    x, states = jax.lax.scan(body2, x, xs)
    return x, states, jnp.zeros((), jnp.float32)


# ---------------------------------------------------------------------------
# Public API: forward / loss / prefill / decode
# ---------------------------------------------------------------------------


def _embed(cfg, params, tokens):
    x = jnp.take(params["embed"], tokens, axis=0)
    return constrain_resid(x)


def forward(cfg, params, tokens, *, frontend=None, bank=None, lora_idx=None,
            window=None, remat=False, lora_kernel="einsum"):
    """Teacher-forced full-sequence forward. Returns (h (B,S,d), aux)."""
    window = cfg.sliding_window if window is None else window
    B, S = tokens.shape
    positions = jnp.arange(S)
    x = _embed(cfg, params, tokens)
    kw = dict(window=window, bank=bank, lora_idx=lora_idx, remat=remat,
              collect=False, lora_kernel=lora_kernel)
    fam = cfg.family
    if fam in ("dense", "moe"):
        h, _, aux = _run_dense_full(cfg, params, x, positions, **kw)
    elif fam == "vlm":
        h, _, aux = _run_vlm_full(cfg, params, x, positions,
                                  frontend=frontend, **kw)
    elif fam == "audio":
        h, _, aux = _run_audio_full(cfg, params, x, positions,
                                    frontend=frontend, **kw)
    elif fam == "hybrid":
        h, _, aux = _run_hybrid_full(cfg, params, x, positions, **kw)
    elif fam == "ssm":
        h, _, aux = _run_rwkv_full(cfg, params, x, bank=bank,
                                   lora_idx=lora_idx, remat=remat,
                                   collect=False, lora_kernel=lora_kernel)
    else:
        raise ValueError(fam)
    return rmsnorm(h, params["ln_f"], cfg.rmsnorm_eps), aux


def loss_fn(cfg, params, batch, *, remat=True, aux_coef=0.01):
    h, aux = forward(cfg, params, batch["tokens"],
                     frontend=batch.get("frontend"), remat=remat)
    loss = chunked_cross_entropy(h, lm_head(cfg, params), batch["labels"])
    return loss + aux_coef * aux


# the cache key of the held-expert layers' decode counters
COUNTERS = "expert_counts"


def init_cache(cfg, batch: int, max_len: int, dtype=jnp.float32,
               enc_len: Optional[int] = None):
    """Zeroed cache pytree. max_len should already account for any sliding
    window (callers pass min(seq, window))."""
    Kv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    cache = {"pos": jnp.zeros((batch,), jnp.int32)}
    n_attn = n_attn_applications(cfg)
    if cfg.mla is not None:
        m = cfg.mla
        cache["c"] = jnp.zeros((cfg.n_layers, batch, max_len,
                                m.kv_lora_rank), dtype)
        cache["kr"] = jnp.zeros((cfg.n_layers, batch, max_len,
                                 m.qk_rope_head_dim), dtype)
    elif n_attn:
        cache["k"] = jnp.zeros((n_attn, batch, max_len, Kv * hd), dtype)
        cache["v"] = jnp.zeros((n_attn, batch, max_len, Kv * hd), dtype)
    n_cross = n_cross_applications(cfg)
    if n_cross:
        M = enc_len or (cfg.encoder.n_frames if cfg.encoder
                        else cfg.n_frontend_tokens)
        cache["xk"] = jnp.zeros((n_cross, batch, M, Kv, hd), dtype)
        cache["xv"] = jnp.zeros((n_cross, batch, M, Kv, hd), dtype)
    if cfg.family == "hybrid":
        cache["ssm"] = jnp.zeros((cfg.n_layers,) +
                                 mamba2_state(cfg, batch).shape, dtype)
    if cfg.moe is not None and cfg.moe.holds_share:
        n_moe = cfg.n_layers - cfg.n_dense_layers
        cache[COUNTERS] = {"pairs": jnp.zeros((n_moe,), jnp.int32),
                           "touched": jnp.zeros((n_moe,), jnp.int32),
                           "steps": jnp.zeros((), jnp.int32)}
    if cfg.family == "ssm":
        st = rwkv6_state(cfg, batch, dtype)
        cache["wkv"] = jnp.zeros((cfg.n_layers,) + st["wkv"].shape,
                                 jnp.float32)
        cache["x_tm"] = jnp.zeros((cfg.n_layers,) + st["x_tm"].shape, dtype)
        cache["x_cm"] = jnp.zeros((cfg.n_layers,) + st["x_cm"].shape, dtype)
    return cache


def _write_prefill_kv(kvs, cache_arr, window):
    """kvs: (L, B, S, ...) computed at prefill; write into cache (L,B,Smax,W)
    honoring ring layout when window > 0 (a token's trailing dims, e.g.
    (Kv, hd), flatten to the cache's line)."""
    L, B, S = kvs.shape[:3]
    kvs = kvs.reshape(L, B, S, -1)
    Smax = cache_arr.shape[2]
    if window and S > Smax:
        # keep the last `Smax` entries at their ring slots
        tail = kvs[:, :, S - Smax:]
        slots = (jnp.arange(S - Smax, S)) % Smax
        return cache_arr.at[:, :, slots].set(tail.astype(cache_arr.dtype))
    return jax.lax.dynamic_update_slice_in_dim(
        cache_arr, kvs[:, :, :Smax].astype(cache_arr.dtype), 0, axis=2)


def prefill(cfg, params, tokens, *, frontend=None, bank=None, lora_idx=None,
            cache_len: Optional[int] = None, window: Optional[int] = None,
            cache_dtype=None, lora_kernel="einsum"):
    """Prefill a batch of same-length rows. Returns (last_logits (B,V), cache)."""
    window = cfg.sliding_window if window is None else window
    B, S = tokens.shape
    cache_len = cache_len or (min(S, window) if window else S)
    positions = jnp.arange(S)
    x = _embed(cfg, params, tokens)
    kw = dict(window=window, bank=bank, lora_idx=lora_idx, remat=False,
              collect=True, lora_kernel=lora_kernel)
    cache_dtype = cache_dtype or params["embed"].dtype
    enc_len = frontend.shape[1] if frontend is not None else None
    cache = init_cache(cfg, B, cache_len, cache_dtype, enc_len=enc_len)
    fam = cfg.family
    if fam in ("dense", "moe"):
        h, kvs, _ = _run_dense_full(cfg, params, x, positions, **kw)
        names = ("c", "kr") if cfg.mla is not None else ("k", "v")
        with jax.named_scope("attention"):
            for n, kv in zip(names, kvs):
                cache[n] = _write_prefill_kv(kv, cache[n], window)
    elif fam == "vlm":
        h, (kvs, xkv), _ = _run_vlm_full(cfg, params, x, positions,
                                         frontend=frontend, **kw)
        cache["k"] = _write_prefill_kv(kvs[0], cache["k"], window)
        cache["v"] = _write_prefill_kv(kvs[1], cache["v"], window)
        cache["xk"] = xkv[0].astype(cache_dtype)
        cache["xv"] = xkv[1].astype(cache_dtype)
    elif fam == "audio":
        h, (kvs, xkv), _ = _run_audio_full(cfg, params, x, positions,
                                           frontend=frontend, **kw)
        cache["k"] = _write_prefill_kv(kvs[0], cache["k"], window)
        cache["v"] = _write_prefill_kv(kvs[1], cache["v"], window)
        cache["xk"] = xkv[0].astype(cache_dtype)
        cache["xv"] = xkv[1].astype(cache_dtype)
    elif fam == "hybrid":
        h, (kvs, states), _ = _run_hybrid_full(cfg, params, x, positions,
                                               **kw)
        cache["k"] = _write_prefill_kv(kvs[0], cache["k"], window)
        cache["v"] = _write_prefill_kv(kvs[1], cache["v"], window)
        cache["ssm"] = states.astype(cache_dtype)
    elif fam == "ssm":
        h, states, _ = _run_rwkv_full(cfg, params, x, bank=bank,
                                      lora_idx=lora_idx, remat=False,
                                      collect=True,
                                      lora_kernel=lora_kernel)
        cache["wkv"] = states["wkv"]
        cache["x_tm"] = states["x_tm"].astype(cache_dtype)
        cache["x_cm"] = states["x_cm"].astype(cache_dtype)
    else:
        raise ValueError(fam)
    cache["pos"] = jnp.full((B,), S, jnp.int32)
    with jax.named_scope("lm_head"):
        h_last = rmsnorm(h[:, -1], params["ln_f"], cfg.rmsnorm_eps)
        logits = h_last.astype(jnp.float32) @ lm_head(cfg, params).astype(
            jnp.float32)
    return logits, cache


# the block matmul weights the decode step can stream: attention (GQA
# and MLA) and the dense SwiGLU, and, in a held-expert layer, the held
# experts and the shared ones
_STREAMED = ("wq", "wk", "wv", "wo", "w_dkv", "w1", "w3", "w2")
_STREAMED_HELD = ("we1", "we3", "we2", "ws1", "ws3", "ws2")
# the scope that slices each kept ffn weight (the rest: "mlp")
_FFN_SCOPES = {"router": "router", "we1": "experts", "we3": "experts",
               "we2": "experts"}


def streams_weights(cfg, params) -> bool:
    """Whether ``decode_step`` streams the stacked block weights through
    ``stream_matmul`` in place of ``x @ w``: a dense or MoE block (GQA
    or MLA attention, a dense SwiGLU, held experts; the sort-scatter
    expert layer and the VLM, audio, hybrid and SSM steps keep their
    dots), fp32 weights, compiled kernels (a TPU, whose
    default-precision dot takes bf16 operands; the CPU's f32 dot does
    not), and no mesh (the sharded engine's weights are split across
    chips)."""
    return (cfg.family in ("dense", "moe")
            and params["blocks"]["attn"]["wq"].dtype == jnp.float32
            and not kernels.default_interpret()
            and current_axis_env().mesh is None)


def kernel_attention(cfg, window) -> bool:
    """Whether ``decode_step`` attends through ``decode_attention``,
    which reads each layer's live KV blocks in place in the stacked
    cache: GQA attention in a dense or MoE block (MLA keeps its latent
    cache and absorbed attention; the VLM, audio and hybrid steps keep
    ``attend_cache``), no sliding window (the ring is not handled),
    compiled kernels (a TPU), and no mesh (the sharded engine's cache
    is split across chips)."""
    return (cfg.family in ("dense", "moe") and cfg.mla is None
            and not window
            and not kernels.default_interpret()
            and current_axis_env().mesh is None)


def _stream_proj(weights, i):
    """The projection hook over layer ``i`` of the stacked ``weights``,
    each read once and in place by ``stream_matmul``; an expert stack
    (L, E, K, N) gives every held expert's product (E, M, N)."""
    def proj(name, x):
        w = weights[name]
        if w.ndim == 4:
            return stream_matmul(x, w, i)
        y = stream_matmul(x.reshape(-1, x.shape[-1]), w, i)
        return y.reshape(x.shape[:-1] + w.shape[-1:])
    return proj


def _decode_stack(cfg, blocks, carry, pos, *, first, stream, kernel, bank,
                  lora_idx, window, mla_absorbed, lora_kernel):
    """One decode step through a stack of blocks whose layer ``i`` is
    layer ``first + i`` of the cache and the bank. ``carry`` is (x, ck,
    cv) or, for a held-expert stack, (x, ck, cv, pairs, touched) with
    the counters per layer of the stack."""
    # The stacked caches ride the scan CARRY rather than xs/ys:
    # while-loop carry state is aliased in place by XLA, so the donated
    # cache is updated without double-buffering the full (L,B,S,...)
    # arrays (§Perf iter 1c). GQA writes the new token's line into the
    # stack and reads the layer there; MLA reads and writes back its
    # layer's latent slice. Weights and bank are sliced per layer in
    # the body, as scan would slice xs, so that each slice carries the
    # scope of the work that reads it. Streamed weights are not sliced:
    # the kernel takes the whole stack and the layer index (a slice fed
    # to it would be materialised).
    counting = len(carry) == 5
    streamed = {}
    if stream:
        names = _STREAMED + (_STREAMED_HELD if cfg.moe is not None
                             and cfg.moe.holds_share else ())
        streamed = {n: w for g in ("attn", "ffn")
                    for n, w in blocks[g].items() if n in names}

    def kept(group):
        return {n: w for n, w in group.items() if n not in streamed}

    ffn_kept = {}                       # scope -> the kept weights it reads
    for n, w in kept(blocks["ffn"]).items():
        ffn_kept.setdefault(_FFN_SCOPES.get(n, "mlp"), {})[n] = w

    def body(carry, _):
        x, ck, cv, *counts, i = carry
        li = i + first if first else i
        bp = _layer_slice({k: v for k, v in blocks.items()
                           if k not in ("attn", "ffn")}, i)
        with jax.named_scope("proj"):
            bp["attn"] = _layer_slice(kept(blocks["attn"]), i)
        bp["ffn"] = {}
        for scope, group in sorted(ffn_kept.items()):
            with jax.named_scope(scope):
                bp["ffn"].update(_layer_slice(group, i))
        proj = _stream_proj(streamed, i) if streamed else None
        lora = None
        if bank is not None:
            with jax.named_scope("lora"):
                bk = _layer_slice(bank, li)
            lora = make_lora_cb(bk, lora_idx, kernel=lora_kernel)
        if cfg.mla is None:
            x, ck, cv, stats = _dense_block_decode(
                cfg, bp, x, ck, cv, pos, window, lora, proj=proj,
                layer=li, kernel=kernel)
        else:
            with jax.named_scope("attention"):
                kc = jax.lax.dynamic_index_in_dim(ck, li, 0, keepdims=False)
                vc = jax.lax.dynamic_index_in_dim(cv, li, 0, keepdims=False)
            x, kc, vc, stats = _dense_block_decode(
                cfg, bp, x, kc, vc, pos, window, lora, mla_absorbed, proj)
            with jax.named_scope("attention"):
                ck = jax.lax.dynamic_update_index_in_dim(
                    ck, kc.astype(ck.dtype), li, 0)
                cv = jax.lax.dynamic_update_index_in_dim(
                    cv, vc.astype(cv.dtype), li, 0)
        if counting:
            with jax.named_scope("router"):
                counts = [c.at[i].add(v) for c, v in zip(counts, stats)]
        return (x, ck, cv, *counts, i + 1), None

    n = jax.tree.leaves(blocks)[0].shape[0]
    out, _ = jax.lax.scan(body, (*carry, jnp.zeros((), jnp.int32)), None,
                          length=n)
    return out[:-1]


def decode_step(cfg, params, cache, tokens, *, bank=None, lora_idx=None,
                window: Optional[int] = None, mla_absorbed=False,
                lora_kernel="einsum"):
    """One decode step. tokens: (B,) int32. Returns (logits (B,V), cache)."""
    window = cfg.sliding_window if window is None else window
    pos = cache["pos"]
    x = _embed(cfg, params, tokens[:, None])
    fam = cfg.family
    new_cache = dict(cache)

    if fam in ("dense", "moe"):
        names = ("c", "kr") if cfg.mla is not None else ("k", "v")
        carry = (x,) + tuple(cache[n] for n in names)
        kw = dict(pos=pos, stream=streams_weights(cfg, params),
                  kernel=kernel_attention(cfg, window), bank=bank,
                  lora_idx=lora_idx, window=window,
                  mla_absorbed=mla_absorbed, lora_kernel=lora_kernel)
        if cfg.n_dense_layers:
            carry = _decode_stack(cfg, params["dense_blocks"], carry,
                                  first=0, **kw)
        counts = cache.get(COUNTERS)
        if counts is not None:
            carry += (counts["pairs"], counts["touched"])
        carry = _decode_stack(cfg, params["blocks"], carry,
                              first=cfg.n_dense_layers, **kw)
        x = carry[0]
        for n, c in zip(names, carry[1:3]):
            new_cache[n] = c
        if counts is not None:
            new_cache[COUNTERS] = {"pairs": carry[3], "touched": carry[4],
                                   "steps": counts["steps"] + 1}
    elif fam == "vlm":
        n_cross = cfg.n_layers // cfg.cross_attn_every
        per = cfg.cross_attn_every - 1
        sb = jax.tree.map(
            lambda t: t.reshape((n_cross, per) + t.shape[1:]),
            params["self_blocks"])
        kk = cache["k"].reshape((n_cross, per) + cache["k"].shape[1:])
        vv = cache["v"].reshape((n_cross, per) + cache["v"].shape[1:])

        def self_body(x, inp):
            bp, kc, vc = inp
            x, kc, vc, _ = _dense_block_decode(cfg, bp, x, kc[None],
                                               vc[None], pos, window, None)
            return x, (kc[0], vc[0])

        def period_body(x, inp):
            blocks_i, cross_bp, kci, vci, xk, xv = inp
            x, (kc2, vc2) = jax.lax.scan(self_body, x, (blocks_i, kci, vci))
            x = _cross_block(cfg, cross_bp, x, xk, xv, None)
            return x, (kc2, vc2)

        x, (k2, v2) = jax.lax.scan(
            period_body, x,
            (sb, params["cross_blocks"], kk, vv, cache["xk"], cache["xv"]))
        new_cache["k"] = k2.reshape(cache["k"].shape)
        new_cache["v"] = v2.reshape(cache["v"].shape)
    elif fam == "audio":
        def body(x, inp):
            bp, kc, vc, xk, xv = inp
            h, (kc, vc) = gqa_decode(cfg, bp["attn"],
                                     rmsnorm(x, bp["ln1"], cfg.rmsnorm_eps),
                                     kc[None], vc[None], pos, window=window)
            x = x + h
            x = x + cross_attend(cfg, bp["cross"],
                                 rmsnorm(x, bp["lnc"], cfg.rmsnorm_eps),
                                 xk, xv)
            x = x + swiglu(bp["ffn"], rmsnorm(x, bp["ln2"], cfg.rmsnorm_eps))
            return x, (kc[0], vc[0])

        x, (k2, v2) = jax.lax.scan(
            body, x, (params["dec_blocks"], cache["k"], cache["v"],
                      cache["xk"], cache["xv"]))
        new_cache["k"], new_cache["v"] = k2, v2
    elif fam == "hybrid":
        ck, cv = cache["k"], cache["v"]
        states = []
        lora = make_lora_cb(_bank_slice(bank, 0) if bank is not None else
                            None, lora_idx, kernel=lora_kernel)
        segs = _hybrid_segments(cfg)

        def mamba_body(x, inp):
            bp, st = inp
            out, st2 = mamba2_step(cfg, bp,
                                   rmsnorm(x, bp["ln"], cfg.rmsnorm_eps), st)
            return x + out, st2

        for i, (start, size) in enumerate(segs):
            x, ck, cv, _ = _dense_block_decode(
                cfg, params["shared_attn"], x, ck, cv, pos, window, lora,
                layer=i)
            sub = jax.tree.map(
                lambda t: jax.lax.slice_in_dim(t, start, start + size),
                params["mamba_blocks"])
            st_in = jax.lax.slice_in_dim(cache["ssm"], start, start + size)
            x, st_out = jax.lax.scan(mamba_body, x, (sub, st_in))
            states.append(st_out)
        new_cache["k"], new_cache["v"] = ck, cv
        new_cache["ssm"] = jnp.concatenate(states, axis=0).astype(
            cache["ssm"].dtype)
    elif fam == "ssm":
        def body(x, inp):
            bp, wkv, x_tm, x_cm, bk = inp
            lora = make_lora_cb(bk, lora_idx, kernel=lora_kernel) \
                if bank is not None else None
            st = {"wkv": wkv, "x_tm": x_tm, "x_cm": x_cm}
            x, st2 = _rwkv_block(cfg, bp, x, st, lora)
            return x, (st2["wkv"], st2["x_tm"], st2["x_cm"])

        if bank is not None:
            xs = (params["blocks"], cache["wkv"], cache["x_tm"],
                  cache["x_cm"], bank)
        else:
            xs = (params["blocks"], cache["wkv"], cache["x_tm"],
                  cache["x_cm"])

        def body2(x, inp):
            if bank is not None:
                bp, wkv, x_tm, x_cm, bk = inp
            else:
                (bp, wkv, x_tm, x_cm), bk = inp, None
            return body(x, (bp, wkv, x_tm, x_cm, bk))

        x, (wkv2, xtm2, xcm2) = jax.lax.scan(body2, x, xs)
        new_cache["wkv"], new_cache["x_tm"], new_cache["x_cm"] = \
            wkv2, xtm2, xcm2
    else:
        raise ValueError(fam)

    new_cache["pos"] = pos + 1
    with jax.named_scope("lm_head"):
        h_last = rmsnorm(x[:, 0], params["ln_f"], cfg.rmsnorm_eps)
        logits = h_last.astype(jnp.float32) @ lm_head(cfg, params).astype(
            jnp.float32)
    return logits, new_cache
