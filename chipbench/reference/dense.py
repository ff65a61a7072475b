"""Plain reference of the dense decoder the benchmark serves, with one
LoRA adapter on the q/k/v/o projections.

Straight ``jax.numpy``, float32, every matmul at the precision the
configuration states (``precision.matmul``: ``"default"``, the TPU's one
pass of bfloat16 products summed in float32, or ``"highest"``), one
sequence at a time, the layers walked by ``lax.scan`` so that only one
layer's activations live at once. It imports nothing of the program.

The block, as the configuration files state it: x + attn(rmsnorm(x)),
then x + swiglu(rmsnorm(x)); grouped-query attention with causal
masking, query head h reading key/value head h // (H / Kv); rotary
embedding on the whole head, the two halves of each head rotated
against each other ("rotate half"); optional q/k/v biases; an untied LM
head after a final RMSNorm. Each projection p adds (x A_p) B_p of the
request's adapter.

Lower precisions, for the control: ``"bfloat16"`` keeps weights,
activations and matmul outputs in bfloat16; ``"float8"`` also rounds
both operands of every linear layer (projections, LoRA, FFN, LM head)
to float8 e4m3, scaled per row of the activations and per output
column of the weights. Norms and softmax stay in float32 in both.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..weights import frozen

F32 = jnp.float32
BF16 = jnp.bfloat16
PRECISIONS = ("float32", "bfloat16", "float8")


def _fp8(x, axis):
    """Round ``x`` to float8 e4m3 with a scale per slice along
    ``axis``, and back to bfloat16."""
    amax = jnp.max(jnp.abs(x.astype(F32)), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, 448.0 / amax, 1.0)
    q = (x.astype(F32) * scale).astype(jnp.float8_e4m3fn)
    return (q.astype(F32) / scale).astype(BF16)


def _linear(precision):
    if precision == "float8":
        return lambda x, w: _fp8(x, -1) @ _fp8(w, 0)
    return lambda x, w: x @ w


def _rmsnorm(x, scale, eps):
    x32 = x.astype(F32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * scale.astype(F32)).astype(x.dtype)


def _rope(x, theta):
    """x: (S, heads, hd), position = row index."""
    S, _, hd = x.shape
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(S, dtype=F32)[:, None] * freqs          # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x.astype(F32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           -1).astype(x.dtype)


def _layer(cfg, mm, x, lp):
    p, ad = lp
    S = x.shape[0]
    H, Kv, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    eps = cfg["rmsnorm_eps"]
    a = p["attn"]

    def proj(x, name, target):
        y = mm(x, a[name]) + mm(mm(x, ad[target]["A"]), ad[target]["B"])
        bias = a.get("b" + target)
        return y if bias is None else y + bias

    h = _rmsnorm(x, p["ln1"], eps)
    q = proj(h, "wq", "q").reshape(S, H, hd)
    k = proj(h, "wk", "k").reshape(S, Kv, hd)
    v = proj(h, "wv", "v").reshape(S, Kv, hd)
    q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    kh = jnp.repeat(k, H // Kv, axis=1)                        # (S, H, hd)
    vh = jnp.repeat(v, H // Kv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, kh).astype(F32) * hd ** -0.5
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    s = jnp.where(causal[None], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1).astype(x.dtype)
    o = jnp.einsum("hqk,khd->qhd", w, vh).reshape(S, H * hd)
    x = x + proj(o, "wo", "o")
    h = _rmsnorm(x, p["ln2"], eps)
    f = p["ffn"]
    x = x + mm(jax.nn.silu(mm(h, f["w1"])) * mm(h, f["w3"]), f["w2"])
    return x, None


@functools.lru_cache(maxsize=None)
def _logits_fn(key: tuple, precision: str, matmul: str):
    cfg = dict(key)
    dtype = F32 if precision == "float32" else BF16
    mm = _linear(precision)

    def run(params, adapter, tokens):
        cast = functools.partial(jax.tree.map, lambda t: t.astype(dtype))
        params, adapter = cast(params), cast(adapter)
        x = params["embed"][tokens]
        x, _ = jax.lax.scan(functools.partial(_layer, cfg, mm), x,
                            (params["blocks"], adapter))
        h = _rmsnorm(x, params["ln_f"], cfg["rmsnorm_eps"])
        return mm(h, params["lm_head"]).astype(F32)

    def at_precision(*args):
        with jax.default_matmul_precision(matmul):
            return run(*args)

    return jax.jit(at_precision)


def logits(cfg: dict, params, adapter, tokens, precision="float32",
           matmul="highest"):
    """(S, V) logits of every position of ``tokens`` (S,) under one
    adapter ``{target: {"A": (L, in, r), "B": (L, r, out)}}``, storing
    in ``precision`` and multiplying at ``matmul`` precision."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    return _logits_fn(frozen(cfg), precision, matmul)(
        params, adapter, jnp.asarray(tokens, jnp.int32))


@jax.jit
def gaps(ref_logits, ids):
    """How far the logit of ``ids`` (S,) lies below the reference's best
    at each position."""
    picked = jnp.take_along_axis(ref_logits, ids[:, None], axis=-1)[:, 0]
    return jnp.max(ref_logits, axis=-1) - picked
