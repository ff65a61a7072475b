"""The dense decoder block the benchmark serves: the architecture
``"dense"`` of a configuration file.

Every architecture-specific piece of the harness comes from one module
like this, loaded by path from ``chipbench/arch/<architecture>.py``, so
a configuration of a new architecture is added as files alone. The
interface:

- ``model_config(cfg)``: the program's ``ModelConfig``;
- ``params(cfg, key, dtype)``: the base weights, in the layout the
  program consumes;
- ``target_dims(cfg, target)``: (in, out) width of a LoRA target;
- ``logits(cfg, params, adapter, tokens, precision=..., matmul=...)``:
  the plain reference, with its lower-precision controls;
- ``block_matmul_params``, ``weight_bytes``, ``kv_bytes_per_token``,
  ``decode_cost(cfg, rows)``, ``prefill_flops(cfg, rows)``: the work
  the algorithm needs;
- ``SCOPES``: the ``jax.named_scope`` names of the program's decode
  step; ``scope_cost(cfg, rows)``: the least work of named scopes of
  one decode step, ``{scope: (operations, bytes)}``.

The block, as the configuration files state it: x + attn(rmsnorm(x)),
then x + swiglu(rmsnorm(x)); grouped-query attention with causal
masking, query head h reading key/value head h // (H / Kv); rotary
embedding on the whole head, the two halves of each head rotated
against each other ("rotate half"); optional q/k/v biases; an untied LM
head after a final RMSNorm. Each projection p adds (x A_p) B_p of the
request's adapter.

The reference is straight ``jax.numpy``, float32, every matmul at the
precision asked for (``"highest"`` in the check; ``"default"`` is the
TPU's one pass of bfloat16 products summed in float32), one sequence at
a time, the layers walked by ``lax.scan`` so that only one layer's
activations live at once. It imports nothing of the program; only
``model_config`` does. Lower precisions, for the control:
``"bfloat16"`` keeps weights, activations and matmul outputs in
bfloat16; ``"float8"`` also rounds both operands of every linear layer
(projections, LoRA, FFN, LM head) to float8 e4m3, scaled per row of the
activations and per output column of the weights. Norms and softmax
stay in float32 in both.

Counts are for one engine step; a multiply-add is two operations. They
count the work, not what the program happens to do: the KV of the live
context (not the whole cache), each distinct adapter of a batch at its
true rank (not the padded bank), and the weights at the configuration's
dtype once per step.
"""
from __future__ import annotations

import functools
from typing import Dict, Iterable, Tuple

import jax
import jax.numpy as jnp

from chipbench.flops import DTYPE_BYTES, adapter_params
from chipbench.weights import frozen, normal, thawed

SCOPES = ("proj", "lora", "attention", "mlp", "lm_head")

F32 = jnp.float32
BF16 = jnp.bfloat16
PRECISIONS = ("float32", "bfloat16", "float8")


# ---------------------------------------------------------------------------
# the program's configuration and weights
# ---------------------------------------------------------------------------


def model_config(cfg: dict):
    """The program's ``ModelConfig`` from the configuration file's own
    numbers."""
    from repro.configs.base import LoRAConfig, ModelConfig
    return ModelConfig(
        name=cfg["name"], family=cfg["family"], n_layers=cfg["n_layers"],
        d_model=cfg["d_model"], n_heads=cfg["n_heads"],
        n_kv_heads=cfg["n_kv_heads"], d_ff=cfg["d_ff"],
        vocab_size=cfg["vocab_size"], head_dim=cfg["head_dim"],
        qkv_bias=cfg["qkv_bias"], rope_theta=cfg["rope_theta"],
        rmsnorm_eps=cfg["rmsnorm_eps"],
        tie_embeddings=cfg["tie_embeddings"],
        lora=LoRAConfig(targets=tuple(cfg["lora_targets"])),
        source=cfg["source"])


def dims(cfg: dict) -> dict:
    H, Kv, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    return {"d": cfg["d_model"], "q": H * hd, "kv": Kv * hd,
            "ff": cfg["d_ff"], "V": cfg["vocab_size"],
            "L": cfg["n_layers"]}


def params(cfg: dict, key, dtype):
    """``{"embed", "ln_f", "lm_head", "blocks": {"ln1", "ln2", "attn",
    "ffn"}}`` with the layer axis leading."""
    k = dims(cfg)
    d, L = k["d"], k["L"]
    ks = iter(jax.random.split(key, 16))
    blocks = {
        "ln1": 1.0 + normal(next(ks), (L, d), 0.1, dtype),
        "ln2": 1.0 + normal(next(ks), (L, d), 0.1, dtype),
        "attn": {
            "wq": normal(next(ks), (L, d, k["q"]), d ** -0.5, dtype),
            "wk": normal(next(ks), (L, d, k["kv"]), d ** -0.5, dtype),
            "wv": normal(next(ks), (L, d, k["kv"]), d ** -0.5, dtype),
            "wo": normal(next(ks), (L, k["q"], d), k["q"] ** -0.5, dtype),
        },
        "ffn": {
            "w1": normal(next(ks), (L, d, k["ff"]), d ** -0.5, dtype),
            "w3": normal(next(ks), (L, d, k["ff"]), d ** -0.5, dtype),
            "w2": normal(next(ks), (L, k["ff"], d), k["ff"] ** -0.5,
                         dtype),
        },
    }
    if cfg["qkv_bias"]:
        blocks["attn"]["bq"] = normal(next(ks), (L, k["q"]), 0.02, dtype)
        blocks["attn"]["bk"] = normal(next(ks), (L, k["kv"]), 0.02, dtype)
        blocks["attn"]["bv"] = normal(next(ks), (L, k["kv"]), 0.02, dtype)
    return {
        "embed": normal(next(ks), (k["V"], d), d ** -0.5, dtype),
        "ln_f": 1.0 + normal(next(ks), (d,), 0.1, dtype),
        "lm_head": normal(next(ks), (d, k["V"]), d ** -0.5, dtype),
        "blocks": blocks,
    }


def target_dims(cfg: dict, target: str) -> tuple:
    """(in, out) width of a LoRA target projection."""
    k = dims(cfg)
    return {"q": (k["d"], k["q"]), "k": (k["d"], k["kv"]),
            "v": (k["d"], k["kv"]), "o": (k["q"], k["d"])}[target]


# ---------------------------------------------------------------------------
# the work the algorithm needs
# ---------------------------------------------------------------------------


def _itemsize(cfg: dict, part: str) -> int:
    return DTYPE_BYTES[cfg["precision"][part]]


def _lora_per_rank(cfg: dict) -> int:
    """A and B widths of all targets of one layer, per unit of rank."""
    return sum(sum(target_dims(cfg, t)) for t in cfg["lora_targets"])


def block_matmul_params(cfg: dict) -> int:
    """Weights one token multiplies through in one layer."""
    k = dims(cfg)
    return (k["d"] * k["q"] + 2 * k["d"] * k["kv"] + k["q"] * k["d"]
            + 3 * k["d"] * k["ff"])


def weight_bytes(cfg: dict) -> int:
    """Every weight a step reads: blocks (with norms and biases), final
    norm and LM head. Embedding rows read per token are counted apart."""
    k = dims(cfg)
    per_layer = block_matmul_params(cfg) + 2 * k["d"]
    if cfg["qkv_bias"]:
        per_layer += k["q"] + 2 * k["kv"]
    n = k["L"] * per_layer + k["d"] + k["d"] * k["V"]
    return n * _itemsize(cfg, "weights")


def kv_bytes_per_token(cfg: dict) -> int:
    k = dims(cfg)
    return 2 * k["L"] * k["kv"] * _itemsize(cfg, "kv_cache")


def token_flops(cfg: dict, rank: int, context: int) -> int:
    """One token through every layer: base matmuls, the LoRA delta at
    ``rank``, and attention over ``context`` keys (QK^T and PV)."""
    k = dims(cfg)
    lora = rank * _lora_per_rank(cfg)
    attn = 2 * context * k["q"]
    return 2 * k["L"] * (block_matmul_params(cfg) + lora) + 2 * k["L"] * attn


def decode_cost(cfg: dict, rows: Iterable[Tuple[str, int, int]]
                ) -> Tuple[int, int]:
    """(operations, bytes) of one decode step over ``rows`` of
    (adapter id, rank, context after this token)."""
    rows = list(rows)
    k = dims(cfg)
    flops = sum(token_flops(cfg, r, c) for _, r, c in rows) \
        + len(rows) * 2 * k["d"] * k["V"]
    adapters = {a: r for a, r, _ in rows}
    nbytes = (weight_bytes(cfg)
              + len(rows) * k["d"] * _itemsize(cfg, "weights")
              + sum(adapter_params(target_dims, cfg, r)
                    for r in adapters.values())
              * _itemsize(cfg, "lora_banks")
              + sum(c - 1 for _, _, c in rows) * kv_bytes_per_token(cfg)
              + len(rows) * kv_bytes_per_token(cfg))
    return flops, nbytes


def prefill_flops(cfg: dict, rows: Iterable[Tuple[str, int, int]]) -> int:
    """Operations of one prefill call over ``rows`` of (adapter id,
    rank, prompt length): causal attention, and LM-head logits for the
    last position only, as prefill returns."""
    k = dims(cfg)
    flops = 0
    for _, r, s in rows:
        lora = r * _lora_per_rank(cfg)
        flops += 2 * k["L"] * s * (block_matmul_params(cfg) + lora)
        flops += 2 * k["L"] * 2 * k["q"] * s * (s + 1) // 2
        flops += 2 * k["d"] * k["V"]
    return flops


def scope_cost(cfg: dict, rows: Iterable[Tuple[str, int, int]]
               ) -> Dict[str, Tuple[int, int]]:
    """(operations, bytes) of the ``lora`` scope of one decode step over
    ``rows`` of (adapter id, rank, context): each row at its adapter's
    true rank, and each distinct adapter's A and B read once at that
    rank."""
    rows = list(rows)
    per_rank = cfg["n_layers"] * _lora_per_rank(cfg)
    flops = sum(2 * r * per_rank for _, r, _ in rows)
    adapters = {a: r for a, r, _ in rows}
    nbytes = sum(r * per_rank for r in adapters.values()) \
        * _itemsize(cfg, "lora_banks")
    return {"lora": (flops, nbytes)}


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------


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
    cfg = thawed(key)
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
