"""Shared model machinery: sharding axis environment, norms, rope,
pure-JAX flash attention (chunked online-softmax), chunked cross-entropy,
and parameter PartitionSpec rules.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import os
import threading
from typing import Optional, Tuple

# Sharding mode for the serving path (§Perf hillclimbing):
#   "baseline" — paper-faithful-first layout: KV cache sharded on kv-heads
#                (replicates when heads % model != 0), LoRA banks
#                TP-sharded on the rank dim (S-LoRA style, paper §III-A.3).
#   "opt"      — beyond-paper: KV cache sharded on the *sequence* dim
#                (context-parallel decode), LoRA banks replicated and
#                applied locally (no per-layer all-reduce).
# Recorded separately in EXPERIMENTS.md §Perf.
SHARDING_MODE = os.environ.get("REPRO_SHARDING", "opt")

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

# ---------------------------------------------------------------------------
# Axis environment: which mesh axes shard batch / model dims. When inactive
# (unit tests, single device) all constraints are no-ops.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AxisEnv:
    batch: Tuple[str, ...] = ()
    model: Optional[str] = None
    mesh: Optional[object] = None      # physical Mesh (for shard_map paths)
    # LoRA sharding scheme for the serving path. None follows
    # SHARDING_MODE (replicated banks in "opt", rank-TP in "baseline");
    # "coshard" is the mesh-sharded engine's scheme: A sharded on
    # d_model, B on d_out, so each shard computes a partial rank-r
    # intermediate that is reduced with ONE psum and the expand output
    # comes out column-sharded like the base projection — the full-width
    # delta is never gathered.
    lora: Optional[str] = None

    @property
    def active(self) -> bool:
        return bool(self.batch) or self.model is not None


_LOCAL = threading.local()


def current_axis_env() -> AxisEnv:
    return getattr(_LOCAL, "env", AxisEnv())


@contextlib.contextmanager
def axis_env(batch: Tuple[str, ...] = (), model: Optional[str] = None,
             mesh=None, lora: Optional[str] = None):
    prev = current_axis_env()
    _LOCAL.env = AxisEnv(tuple(batch), model, mesh, lora)
    try:
        yield _LOCAL.env
    finally:
        _LOCAL.env = prev


def _resolve(dim, env: AxisEnv):
    if dim == "batch":
        return env.batch if len(env.batch) != 1 else env.batch[0]
    if dim == "model":
        return env.model
    return None


def constrain(x: jax.Array, *dims) -> jax.Array:
    """with_sharding_constraint under the ambient axis env.

    dims entries: "batch" | "model" | None, one per array dim.
    """
    env = current_axis_env()
    if not env.active:
        return x
    spec = P(*[_resolve(d, env) for d in dims])
    return jax.lax.with_sharding_constraint(x, spec)


def constrain_resid(x: jax.Array) -> jax.Array:
    """Residual-stream (B,S,d) constraint. In "sp" mode the sequence dim
    is sharded over the model axis (Megatron sequence parallelism):
    norms/adds run 1/n-local and each block boundary is an all-gather +
    reduce-scatter pair instead of a full all-reduce of a replicated
    stream (§Perf iter 3a)."""
    env = current_axis_env()
    if not env.active:
        return x
    if SHARDING_MODE == "sp" and env.model is not None and x.ndim == 3 \
            and env.mesh is not None \
            and x.shape[1] % env.mesh.shape[env.model] == 0:
        return jax.lax.with_sharding_constraint(
            x, P(_resolve("batch", env), env.model, None))
    return jax.lax.with_sharding_constraint(
        x, P(_resolve("batch", env), None, None))


# ---------------------------------------------------------------------------
# Param PartitionSpec rules, keyed on leaf name (last path component).
# Spec applies to TRAILING dims; leading (stacked-layer) dims get None.
# ---------------------------------------------------------------------------

_COL = {"wq", "wk", "wv", "w1", "w3", "w_xz", "w_r", "w_k", "w_v", "w_g",
        "wk_cm", "w_uk", "w_uv", "lm_head", "ws1", "ws3"}
_ROW = {"wo", "w2", "w_out", "w_o", "wv_cm", "ws2"}
_EXPERT = {"we1", "we2", "we3"}
_EMBED = {"embed"}
_VEC_COL = {"bq", "bk", "bv", "ln_y"}


def _tail_spec(name: str, ndim_tail: int):
    if name == "A":                      # LoRA shrink bank (Na, d, r)
        # baseline: S-LoRA TP split on the rank dim; opt: replicated
        # (banks are tiny; local application avoids a (B,S,out)
        # all-reduce per target per layer — §Perf iteration 3)
        return (None, None, "model") if SHARDING_MODE == "baseline" \
            else (None, None, None)
    if name == "B":                      # LoRA expand bank (Na, r, out)
        return (None, "model", None) if SHARDING_MODE == "baseline" \
            else (None, None, None)
    if name in _COL:
        return (None, "model")
    if name in _ROW:
        return ("model", None)
    if name in _EXPERT:
        return ("model", None, None)
    if name in _EMBED:
        return ("model", None)
    if name in _VEC_COL:
        return ("model",)
    return ()


def param_pspecs(params, model_axis: str = "model"):
    """PartitionSpec tree for a param tree, from leaf-name rules."""

    def spec_for(path, leaf):
        name = None
        for entry in reversed(path):
            if isinstance(entry, jax.tree_util.DictKey):
                name = str(entry.key)
                break
        tail = _tail_spec(name, leaf.ndim) if name else ()
        tail = tail[-leaf.ndim:] if leaf.ndim < len(tail) else tail
        full = (None,) * (leaf.ndim - len(tail)) + tuple(
            model_axis if t == "model" else None for t in tail)
        return P(*full)

    return jax.tree_util.tree_map_with_path(spec_for, params)


# ---------------------------------------------------------------------------
# LoRA callback contract
# ---------------------------------------------------------------------------
# Blocks call ``lora(name, x) -> delta`` with x: (B, S, d_target) for a
# projection target name in {"q","k","v","o"}; the callback owns the
# adapter gather and returns the batched LoRA delta in x.dtype.
# ``repro.lora.batched.make_lora_cb`` builds the callback from a bank
# layer slice in either execution form (gather-einsum, or the fused
# Pallas SGMV kernels over the token-major flattening below).

LoRACallback = "Callable[[str, jax.Array], jax.Array]"


def rows_to_tokens(x: jax.Array):
    """(B, S, d) -> ((B*S, d), (B, S)): the token-major flattening the
    SGMV kernel path consumes (row-major, so token t of row b sits at
    b*S + t and per-row adapter ids repeat S times)."""
    B, S, d = x.shape
    return x.reshape(B * S, d), (B, S)


def tokens_to_rows(y: jax.Array, B: int, S: int) -> jax.Array:
    """Inverse of ``rows_to_tokens`` for the (B*S, d_out) kernel output."""
    return y.reshape(B, S, y.shape[-1])


# ---------------------------------------------------------------------------
# Norms / rope / init
# ---------------------------------------------------------------------------


def rmsnorm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    out = x32 * jax.lax.rsqrt(var + eps)
    return (out * scale.astype(jnp.float32)).astype(x.dtype)


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def yarn_correction_range(dim: int, theta: float, scaling):
    """(low, high) rotary dims of YaRN's blend: below ``low`` the
    frequencies are kept, above ``high`` divided by the factor."""
    def corr(rotations):
        return dim * math.log(scaling.original_max_position
                              / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))
    low = max(math.floor(corr(scaling.beta_fast)), 0)
    high = min(math.ceil(corr(scaling.beta_slow)), dim - 1)
    return low, high


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_freqs(dim: int, theta: float, scaling) -> jax.Array:
    """YaRN's inverse frequencies: each of the ``dim/2`` pairs blends
    the plain frequency (weight ``mask``) with the plain one over the
    factor, the mask ramping from 1 to 0 across the correction range."""
    plain = rope_freqs(dim, theta)
    low, high = yarn_correction_range(dim, theta, scaling)
    ramp = (jnp.arange(dim // 2, dtype=jnp.float32) - low) \
        / max(high - low, 0.001)
    mask = 1.0 - jnp.clip(ramp, 0.0, 1.0)
    return plain / scaling.factor * (1.0 - mask) + plain * mask


def apply_rope(x: jax.Array, positions: jax.Array, theta: float, *,
               freqs: Optional[jax.Array] = None,
               interleaved: bool = False) -> jax.Array:
    """x: (..., S, H, hd); positions: broadcastable to (..., S).
    ``freqs`` replaces the plain inverse frequencies (YaRN). The halves
    of each head rotate against each other ("rotate half"), or, with
    ``interleaved``, each even dim against the odd dim after it, the
    result laid out evens first (DeepSeek-V2's de-interleave)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta) if freqs is None else freqs  # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (...,S,hd/2)
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    xf = x.astype(jnp.float32)
    if interleaved:
        x1, x2 = xf[..., 0::2], xf[..., 1::2]
    else:
        x1, x2 = jnp.split(xf, 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def dense_init(key, shape, fan_in=None, dtype=jnp.float32):
    fan_in = fan_in if fan_in is not None else shape[0]
    return (jax.random.normal(key, shape) / jnp.sqrt(fan_in)).astype(dtype)


def dense_proj(p):
    """The plain projection hook ``proj(name, x) -> x @ p[name]`` over
    one layer's weights ``p``."""
    return lambda name, x: x @ p[name]


# ---------------------------------------------------------------------------
# Flash attention (pure JAX, chunked online softmax). Bounds peak memory to
# O(B * H * chunk_q * chunk_k) so 32k prefill lowers within HBM.
# ---------------------------------------------------------------------------

NEG_INF = -1e30


def flash_attention(q, k, v, *, causal: bool, q_positions, k_positions,
                    window: int = 0, chunk_q: int = 512, chunk_k: int = 1024,
                    scale: Optional[float] = None, extra_qk=None):
    """q: (B,Sq,H,hd); k,v: (B,Sk,Kv,hd). GQA via head grouping.

    Masking: causal (q_pos >= k_pos) and optional sliding window
    (q_pos - k_pos < window). Positions are int arrays (Sq,), (Sk,).
    Returns (B,Sq,H,hd) in q.dtype.

    extra_qk: optional (q2 (B,Sq,H,hd2), k2 (B,Sk,hd2)) pair added to the
    scores — MLA's shared rope key. Scoring it as a separate einsum (k2
    has no head dim) avoids materializing broadcast+concat keys, which
    otherwise reshards a (B,*,H,ck) scores tensor inside the kv scan
    (§Perf iter 2d).
    """
    B, Sq, H, hd = q.shape
    _, Sk, Kv, _ = k.shape
    hdv = v.shape[-1]
    G = H // Kv
    scale = scale if scale is not None else 1.0 / (hd ** 0.5)

    cq = min(chunk_q, Sq)
    ck = min(chunk_k, Sk)
    pad_q = (-Sq) % cq
    pad_k = (-Sk) % ck
    qp = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    vpd = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    if extra_qk is not None:
        q2, k2 = extra_qk
        hd2 = q2.shape[-1]
        q2p = jnp.pad(q2, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        k2p = jnp.pad(k2, ((0, 0), (0, pad_k), (0, 0)))
    qpos = jnp.pad(q_positions.astype(jnp.int32), (0, pad_q),
                   constant_values=-1)
    kpos = jnp.pad(k_positions.astype(jnp.int32), (0, pad_k),
                   constant_values=2 ** 30)
    nq, nk = qp.shape[1] // cq, kp.shape[1] // ck

    # (B, nq, cq, Kv, G, hd)
    # fp32 score path (iter 2c tried storage-dtype K/V with per-chunk
    # upcast: it regressed dense GQA training 35% — XLA's backward adds
    # convert+reshard pairs around the scan — so fp32 stays; the
    # shard_map path (run_flash) keeps everything local either way)
    qp = (qp.reshape(B, nq, cq, Kv, G, hd).astype(jnp.float32) * scale)
    kp = kp.reshape(B, nk, ck, Kv, hd).astype(jnp.float32)
    vp = vpd.reshape(B, nk, ck, Kv, hdv).astype(jnp.float32)
    if extra_qk is not None:
        q2p = (q2p.reshape(B, nq, cq, Kv, G, hd2).astype(jnp.float32)
               * scale)
        k2p = k2p.reshape(B, nk, ck, hd2).astype(jnp.float32)
    qpos = qpos.reshape(nq, cq)
    kpos = kpos.reshape(nk, ck)

    def body(carry, inp):
        m, l, acc = carry                       # (B,nq,cq,Kv,G) / +hd
        if extra_qk is not None:
            kc, vc, k2c, kposc = inp
        else:
            kc, vc, kposc = inp                 # (B,ck,Kv,hd), (ck,)
        s = jnp.einsum("bqckgh,bzkh->bqckgz", qp, kc.astype(qp.dtype),
                       preferred_element_type=jnp.float32)   # z = ck
        if extra_qk is not None:
            s = s + jnp.einsum("bqckgh,bzh->bqckgz", q2p,
                               k2c.astype(q2p.dtype),
                               preferred_element_type=jnp.float32)
        mask = jnp.ones((nq, cq, ck), dtype=bool)
        if causal:
            mask &= qpos[:, :, None] >= kposc[None, None, :]
        if window:
            mask &= (qpos[:, :, None] - kposc[None, None, :]) < window
        mask &= kposc[None, None, :] < 2 ** 30
        s = jnp.where(mask[None, :, :, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bqckgz,bzkh->bqckgh", p.astype(vc.dtype),
                        vc, preferred_element_type=jnp.float32)
        acc_new = acc * corr[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, nq, cq, Kv, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, nq, cq, Kv, G), jnp.float32)
    a0 = jnp.zeros((B, nq, cq, Kv, G, hdv), jnp.float32)
    if extra_qk is not None:
        xs = (kp.transpose(1, 0, 2, 3, 4), vp.transpose(1, 0, 2, 3, 4),
              k2p.transpose(1, 0, 2, 3), kpos)
    else:
        xs = (kp.transpose(1, 0, 2, 3, 4), vp.transpose(1, 0, 2, 3, 4),
              kpos)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), xs)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = out.reshape(B, nq * cq, H, hdv)[:, :Sq]
    return out.astype(q.dtype)


def attend_cache(q, k_cache, v_cache, valid_mask, scale=None):
    """Single-token decode attention against a KV cache.

    q: (B,1,H,hd); caches: (B,S,Kv,hd); valid_mask: (B,S) bool.
    """
    B, _, H, hd = q.shape
    Kv = k_cache.shape[2]
    hdv = v_cache.shape[-1]
    G = H // Kv
    scale = scale if scale is not None else 1.0 / (hd ** 0.5)
    # keep the big cache operands in their storage dtype; accumulate the
    # contractions in fp32 (§Perf iter 1b: materializing fp32 copies of a
    # sequence-length cache doubles decode HBM traffic)
    qf = (q.reshape(B, Kv, G, hd) * scale).astype(k_cache.dtype)
    s = jnp.einsum("bkgh,bskh->bkgs", qf, k_cache,
                   preferred_element_type=jnp.float32)
    s = jnp.where(valid_mask[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskh->bkgh", p.astype(v_cache.dtype), v_cache,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, 1, H, hdv).astype(q.dtype)


# ---------------------------------------------------------------------------
# Chunked cross entropy — never materializes (B,S,V) logits.
# ---------------------------------------------------------------------------


def chunked_cross_entropy(h, lm_head, labels, chunk: int = 256):
    """h: (B,S,d); lm_head: (d,V); labels: (B,S) int32. Mean NLL."""
    B, S, d = h.shape
    c = min(chunk, S)
    pad = (-S) % c
    hp = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
    lp = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
    n = hp.shape[1] // c
    hp = hp.reshape(B, n, c, d).transpose(1, 0, 2, 3)
    lp = lp.reshape(B, n, c).transpose(1, 0, 2)

    def body(tot, inp):
        hc, lc = inp
        logits = jnp.einsum("bcd,dv->bcv", hc.astype(jnp.float32),
                            lm_head.astype(jnp.float32))
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(
            logits, jnp.maximum(lc, 0)[..., None], axis=-1)[..., 0]
        valid = (lc >= 0).astype(jnp.float32)
        nll = (lse - tgt) * valid
        return tot + jnp.sum(nll), None

    tot, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (hp, lp))
    return tot / (B * S)
