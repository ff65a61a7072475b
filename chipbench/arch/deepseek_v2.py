"""DeepSeek-V2 (the architecture ``"deepseek_v2"`` of a configuration
file): multi-head latent attention with YaRN rotary scaling, leading
dense layers, then layers of routed and shared experts, on one chip's
share of the experts. The interface is ``dense.py``'s.

The configuration file keeps the release's ``config.json`` keys
(``hidden_size``, ``kv_lora_rank``, ``n_routed_experts``, ...). Its
``n_routed_experts`` counts the experts this chip holds, ids
``first_held_expert`` .. ``first_held_expert + n_routed_experts - 1``
of the ``router_experts`` the router scores; ``num_hidden_layers`` (and
``n_layers``, which the harness reads) counts the layers held.

The block, written out from the release and its paper (arXiv:2405.04434):

- x + attn(rmsnorm(x)), then x + ffn(rmsnorm(x)); RMSNorm eps
  ``rms_norm_eps`` everywhere, ``kv_a_layernorm`` included.
- Attention: q = x Wq (no q compression), split per head into nope
  (``qk_nope_head_dim``) and rope (``qk_rope_head_dim``) dims;
  [c | k_pe] = x W_kv_a, c normalised; [k_nope | v] per head = c
  W_kv_b; k_pe one rope key shared by all heads. The rope dims rotate
  each even dim against the odd dim after it (the release
  de-interleaves them, then rotates half), at YaRN's frequencies:
  correction range floor/ceil(dim ln(orig / (2 pi r)) / (2 ln theta))
  for r = beta_fast, beta_slow, mask 1 - clamp((i - low)/(high - low),
  0, 1), inv = inv/factor (1 - mask) + inv mask; cos and sin scaled by
  mscale(factor, mscale)/mscale(factor, mscale_all_dim); softmax
  scale (nope + rope)^-0.5 mscale(factor, mscale_all_dim)^2 with
  mscale(s, m) = 0.1 m ln s + 1. Causal softmax attention over
  q_nope.k_nope + q_pe.k_pe; out = o Wo.
- FFN: the first ``first_k_dense_replace`` layers a SwiGLU of
  ``intermediate_size``; the rest a softmax gate over all
  ``router_experts`` logits, greedy top-``num_experts_per_tok``
  (renormalised only where ``norm_topk_prob``, times
  ``routed_scaling_factor``); each held expert's SwiGLU of
  ``moe_intermediate_size`` weighted by its gate weight where the token
  chose it; the experts not held here add nothing (they lie on the
  deployment's other chips); plus the shared experts, one SwiGLU of
  ``n_shared_experts * moe_intermediate_size``, for every token.
- LoRA: q, kv_a, kv_b (the [k_nope | v] per head layout) and o each
  add (x A) B of the request's adapter.

The reference is straight ``jax.numpy``, float32, at the precision
asked for, one sequence at a time, each stack walked by ``lax.scan``;
it imports nothing of the program; ``model_config`` and the check at
import take the program's configuration classes. Its
lower precisions are ``dense.py``'s: ``"bfloat16"`` storage, and
``"float8"`` operands of every linear layer (the router included).

Counts are for one engine step, a multiply-add two operations, and
count the work: decode through the latent cache (absorbed: per head,
q_nope W_UK^T and o_lat W_UV, and over each live key the latent and
rope scores and the latent sum), each distinct adapter at its true
rank, and of the held experts only what the routing asks. The host
does not see the routing, so that is its expectation under uniform
routing (``held_pairs``, ``held_touched``), which the program's
``ServingEngine.expert_counters`` lets a chip run check.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Iterable, Tuple

import jax
import jax.numpy as jnp

from chipbench.flops import DTYPE_BYTES, adapter_params
from chipbench.weights import frozen, normal, thawed

# The program's configuration has to take YaRN and a held-expert share:
# a checkout whose program lacks them refuses the cell as it loads
# (``load_cell``), before any weight is drawn.
from repro.configs.base import RopeScaling  # noqa: E402

SCOPES = ("proj", "lora", "attention", "router", "experts", "mlp",
          "lm_head")

F32 = jnp.float32
BF16 = jnp.bfloat16
PRECISIONS = ("float32", "bfloat16", "float8")

# what the release fixes and this module does not take otherwise
_FIXED = {"hidden_act": "silu", "attention_bias": False, "n_group": 1,
          "topk_group": 1, "topk_method": "greedy",
          "scoring_func": "softmax", "moe_layer_freq": 1,
          "q_lora_rank": None}


# ---------------------------------------------------------------------------
# the program's configuration and weights
# ---------------------------------------------------------------------------


def model_config(cfg: dict):
    """The program's ``ModelConfig`` from the configuration file's own
    numbers."""
    from repro.configs.base import (LoRAConfig, MLAConfig, ModelConfig,
                                    MoEConfig)
    for k, v in _FIXED.items():
        if cfg.get(k) != v:
            raise ValueError(f"{cfg['name']}: {k} {cfg.get(k)!r}; the "
                             f"deepseek_v2 module takes {v!r}")
    if cfg["n_layers"] != cfg["num_hidden_layers"]:
        raise ValueError("n_layers and num_hidden_layers differ")
    rs = cfg["rope_scaling"]
    return ModelConfig(
        name=cfg["name"], family="moe", n_layers=cfg["n_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["moe_intermediate_size"], vocab_size=cfg["vocab_size"],
        rope_theta=float(cfg["rope_theta"]),
        rmsnorm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"],
        n_dense_layers=cfg["first_k_dense_replace"],
        dense_d_ff=cfg["intermediate_size"],
        mla=MLAConfig(kv_lora_rank=cfg["kv_lora_rank"],
                      qk_nope_head_dim=cfg["qk_nope_head_dim"],
                      qk_rope_head_dim=cfg["qk_rope_head_dim"],
                      v_head_dim=cfg["v_head_dim"], q_lora_rank=0),
        moe=MoEConfig(n_experts=cfg["router_experts"],
                      top_k=cfg["num_experts_per_tok"],
                      d_ff_expert=cfg["moe_intermediate_size"],
                      n_shared_experts=cfg["n_shared_experts"],
                      n_held=cfg["n_routed_experts"],
                      first_held=cfg["first_held_expert"],
                      norm_topk=cfg["norm_topk_prob"],
                      routed_scale=float(cfg["routed_scaling_factor"])),
        rope_scaling=RopeScaling(
            factor=float(rs["factor"]),
            original_max_position=rs["original_max_position_embeddings"],
            beta_fast=float(rs["beta_fast"]),
            beta_slow=float(rs["beta_slow"]), mscale=float(rs["mscale"]),
            mscale_all_dim=float(rs["mscale_all_dim"])),
        lora=LoRAConfig(targets=tuple(cfg["lora_targets"])),
        source=cfg["source"])


def dims(cfg: dict) -> dict:
    H = cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    L, nd = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    return {"d": cfg["hidden_size"], "H": H, "nope": nope, "rope": rope,
            "v": cfg["v_head_dim"], "R": cfg["kv_lora_rank"],
            "q": H * (nope + rope), "kvb": H * (nope + cfg["v_head_dim"]),
            "o": H * cfg["v_head_dim"], "F": cfg["intermediate_size"],
            "f": cfg["moe_intermediate_size"],
            "S": cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
            "E": cfg["router_experts"], "Eh": cfg["n_routed_experts"],
            "first": cfg["first_held_expert"],
            "K": cfg["num_experts_per_tok"], "V": cfg["vocab_size"],
            "L": L, "nd": nd, "nm": L - nd}


def params(cfg: dict, key, dtype):
    """``{"embed", "ln_f", "lm_head", "dense_blocks", "blocks"}``, each
    stack ``{"ln1", "ln2", "attn", "ffn"}`` with the layer axis
    leading: attention ``wq``, ``w_dkv``, ``ln_kv``, ``w_uk`` and
    ``w_uv`` (W_kv_b's k_nope and v columns), ``wo``; a dense ffn
    ``w1``/``w3``/``w2``; an expert ffn ``router`` (all experts),
    ``we1``/``we3``/``we2`` (the held experts) and ``ws1``/``ws3``/
    ``ws2`` (the shared ones)."""
    k = dims(cfg)
    d = k["d"]
    ks = iter(jax.random.split(key, 32))

    def w(shape, fan_in):
        return normal(next(ks), shape, fan_in ** -0.5, dtype)

    def attn(n):
        return {"wq": w((n, d, k["q"]), d),
                "w_dkv": w((n, d, k["R"] + k["rope"]), d),
                "ln_kv": 1.0 + normal(next(ks), (n, k["R"]), 0.1, dtype),
                "w_uk": w((n, k["R"], k["H"] * k["nope"]), k["R"]),
                "w_uv": w((n, k["R"], k["o"]), k["R"]),
                "wo": w((n, k["o"], d), k["o"])}

    def block(n, ffn):
        return {"ln1": 1.0 + normal(next(ks), (n, d), 0.1, dtype),
                "ln2": 1.0 + normal(next(ks), (n, d), 0.1, dtype),
                "attn": attn(n), "ffn": ffn}

    nd, nm, Eh = k["nd"], k["nm"], k["Eh"]
    dense = {"w1": w((nd, d, k["F"]), d), "w3": w((nd, d, k["F"]), d),
             "w2": w((nd, k["F"], d), k["F"])}
    moe = {"router": w((nm, d, k["E"]), d),
           "we1": w((nm, Eh, d, k["f"]), d),
           "we3": w((nm, Eh, d, k["f"]), d),
           "we2": w((nm, Eh, k["f"], d), k["f"]),
           "ws1": w((nm, d, k["S"]), d), "ws3": w((nm, d, k["S"]), d),
           "ws2": w((nm, k["S"], d), k["S"])}
    return {
        "embed": w((k["V"], d), d),
        "ln_f": 1.0 + normal(next(ks), (d,), 0.1, dtype),
        "lm_head": w((d, k["V"]), d),
        "dense_blocks": block(nd, dense),
        "blocks": block(nm, moe),
    }


def target_dims(cfg: dict, target: str) -> tuple:
    """(in, out) width of a LoRA target projection."""
    k = dims(cfg)
    return {"q": (k["d"], k["q"]), "kv_a": (k["d"], k["R"] + k["rope"]),
            "kv_b": (k["R"], k["kvb"]), "o": (k["o"], k["d"])}[target]


# ---------------------------------------------------------------------------
# the work the algorithm needs
# ---------------------------------------------------------------------------


def _itemsize(cfg: dict, part: str) -> int:
    return DTYPE_BYTES[cfg["precision"][part]]


def _lora_per_rank(cfg: dict) -> int:
    """A and B widths of all targets of one layer, per unit of rank."""
    return sum(sum(target_dims(cfg, t)) for t in cfg["lora_targets"])


def attn_params(cfg: dict) -> int:
    """Attention weights of one layer (W_kv_b's as many as one token
    multiplies through in absorbed decode)."""
    k = dims(cfg)
    return (k["d"] * k["q"] + k["d"] * (k["R"] + k["rope"])
            + k["R"] * k["kvb"] + k["o"] * k["d"])


def expert_params(cfg: dict) -> int:
    k = dims(cfg)
    return 3 * k["d"] * k["f"]


def held_pairs(cfg: dict, rows: int) -> float:
    """Expected (token, held expert) pairs of ``rows`` tokens under
    uniform routing: each token picks K of E, Eh of them held."""
    k = dims(cfg)
    return rows * k["K"] * k["Eh"] / k["E"]


def held_touched(cfg: dict, rows: int) -> float:
    """Expected held experts that at least one of ``rows`` tokens picks
    under uniform routing: a token passes over a given expert with
    probability (E - K)/E."""
    k = dims(cfg)
    return k["Eh"] * (1.0 - ((k["E"] - k["K"]) / k["E"]) ** rows)


def block_matmul_params(cfg: dict) -> int:
    """Weights one token multiplies through in all layers: attention,
    the dense layers' SwiGLU, and per expert layer the router, the
    shared experts and the held experts' expected share of its K."""
    k = dims(cfg)
    moe = k["d"] * k["E"] + 3 * k["d"] * k["S"]
    return (k["L"] * attn_params(cfg) + k["nd"] * 3 * k["d"] * k["F"]
            + k["nm"] * moe)


def _routed_flops(cfg: dict, rows: int) -> float:
    return 2 * dims(cfg)["nm"] * held_pairs(cfg, rows) * expert_params(cfg)


def weight_bytes(cfg: dict, rows=None) -> int:
    """Every weight a step reads: blocks (with norms), final norm and LM
    head; of the held experts all, or, given ``rows``, those the rows
    touch (``held_touched``). Embedding rows are counted apart."""
    k = dims(cfg)
    d = k["d"]
    norms = k["L"] * (2 * d + k["R"]) + d
    experts = k["Eh"] if rows is None else held_touched(cfg, rows)
    n = (k["L"] * attn_params(cfg) + k["nd"] * 3 * d * k["F"]
         + k["nm"] * (d * k["E"] + 3 * d * k["S"]
                      + experts * expert_params(cfg))
         + norms + d * k["V"])
    return int(n * _itemsize(cfg, "weights"))


def kv_bytes_per_token(cfg: dict) -> int:
    k = dims(cfg)
    return k["L"] * (k["R"] + k["rope"]) * _itemsize(cfg, "kv_cache")


def _attn_decode_flops(cfg: dict, context: int) -> int:
    """Absorbed attention of one token over ``context`` keys, all
    layers: latent and rope scores, and the latent sum."""
    k = dims(cfg)
    return 2 * k["L"] * context * k["H"] * (2 * k["R"] + k["rope"])


def decode_cost(cfg: dict, rows: Iterable[Tuple[str, int, int]]
                ) -> Tuple[int, int]:
    """(operations, bytes) of one decode step over ``rows`` of
    (adapter id, rank, context after this token)."""
    rows = list(rows)
    k = dims(cfg)
    n = len(rows)
    per_rank = k["L"] * _lora_per_rank(cfg)
    flops = (2 * n * block_matmul_params(cfg) + _routed_flops(cfg, n)
             + sum(2 * r * per_rank + _attn_decode_flops(cfg, c)
                   for _, r, c in rows)
             + n * 2 * k["d"] * k["V"])
    adapters = {a: r for a, r, _ in rows}
    nbytes = (weight_bytes(cfg, n)
              + n * k["d"] * _itemsize(cfg, "weights")
              + sum(adapter_params(target_dims, cfg, r)
                    for r in adapters.values())
              * _itemsize(cfg, "lora_banks")
              + sum(c - 1 for _, _, c in rows) * kv_bytes_per_token(cfg)
              + n * kv_bytes_per_token(cfg))
    return int(flops), int(nbytes)


def prefill_flops(cfg: dict, rows: Iterable[Tuple[str, int, int]]) -> int:
    """Operations of one prefill call over ``rows`` of (adapter id,
    rank, prompt length): the latent expanded per key, causal attention
    of q_nope.k_nope + q_pe.k_pe and the value sum, and LM-head logits
    for the last position only, as prefill returns."""
    k = dims(cfg)
    flops = 0.0
    for _, r, s in rows:
        flops += 2 * s * (block_matmul_params(cfg)
                          + r * k["L"] * _lora_per_rank(cfg))
        flops += _routed_flops(cfg, s)
        flops += 2 * k["L"] * k["H"] * (k["nope"] + k["rope"] + k["v"]) \
            * s * (s + 1) // 2
        flops += 2 * k["d"] * k["V"]
    return int(flops)


def scope_cost(cfg: dict, rows: Iterable[Tuple[str, int, int]]
               ) -> Dict[str, Tuple[int, int]]:
    """(operations, bytes) of the ``lora`` and ``experts`` scopes of
    one decode step over ``rows`` of (adapter id, rank, context).

    ``lora``: each row at its adapter's true rank, each distinct
    adapter's A and B read once at that rank. ``experts``: the held
    experts' SwiGLUs over the pairs routed to them, and the weights of
    the held experts some row picks, read once. The host does not see
    the routing, so both take its expectation under uniform routing:
    R rows make R K Eh/E pairs (R 6 8/64 here) and touch Eh (1 - ((E -
    K)/E)^R) of the held experts (8 (1 - (58/64)^R)), each 3 d f
    weights, per expert layer. A chip run checks the second against
    the program's ``ServingEngine.expert_counters``."""
    rows = list(rows)
    k = dims(cfg)
    per_rank = k["L"] * _lora_per_rank(cfg)
    lora_flops = sum(2 * r * per_rank for _, r, _ in rows)
    adapters = {a: r for a, r, _ in rows}
    lora_bytes = sum(r * per_rank for r in adapters.values()) \
        * _itemsize(cfg, "lora_banks")
    expert_bytes = k["nm"] * held_touched(cfg, len(rows)) \
        * expert_params(cfg) * _itemsize(cfg, "weights")
    return {"lora": (lora_flops, lora_bytes),
            "experts": (int(_routed_flops(cfg, len(rows))),
                        int(expert_bytes))}


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


def _mscale(s, m):
    return 1.0 if s <= 1 else 0.1 * m * math.log(s) + 1.0


def yarn(cfg: dict):
    """(inverse frequencies (rope/2,), cos/sin scale, softmax scale)."""
    rs, dim = cfg["rope_scaling"], cfg["qk_rope_head_dim"]
    base, orig = float(cfg["rope_theta"]), \
        rs["original_max_position_embeddings"]

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    inv = 1.0 / base ** (jnp.arange(0, dim, 2, dtype=F32) / dim)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=F32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    mask = 1.0 - ramp
    inv = inv / rs["factor"] * (1.0 - mask) + inv * mask
    cs = _mscale(rs["factor"], rs["mscale"]) \
        / _mscale(rs["factor"], rs["mscale_all_dim"])
    sm = (cfg["qk_nope_head_dim"] + dim) ** -0.5 \
        * _mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    return inv, cs, sm


def _rope(x, inv, cs):
    """x: (S, heads, dim), position = row index: de-interleave each
    head's dims (evens, then odds), then rotate the halves."""
    S, n, dim = x.shape
    ang = jnp.arange(S, dtype=F32)[:, None] * inv            # (S, dim/2)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :] * cs
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :] * cs
    x = x.astype(F32).reshape(S, n, dim // 2, 2).swapaxes(-1, -2)
    x = x.reshape(S, n, dim)
    rot = jnp.concatenate([-x[..., dim // 2:], x[..., :dim // 2]], -1)
    return x * cos + rot * sin


def _attention(cfg, mm, h, a, ad, rope):
    k = dims(cfg)
    S = h.shape[0]
    H, nope, rd = k["H"], k["nope"], k["rope"]
    eps = cfg["rms_norm_eps"]
    inv, cs, sm = rope

    def proj(x, w, target):
        return mm(x, w) + mm(mm(x, ad[target]["A"]), ad[target]["B"])

    q = proj(h, a["wq"], "q").reshape(S, H, nope + rd)
    ckv = proj(h, a["w_dkv"], "kv_a")
    c = _rmsnorm(ckv[:, :k["R"]], a["ln_kv"], eps)
    k_pe = _rope(ckv[:, None, k["R"]:], inv, cs)               # (S, 1, rd)
    # W_kv_b in the release's layout: per head [k_nope | v]
    w_kvb = jnp.concatenate(
        [a["w_uk"].reshape(k["R"], H, nope),
         a["w_uv"].reshape(k["R"], H, k["v"])], -1).reshape(k["R"], -1)
    kv = proj(c, w_kvb, "kv_b").reshape(S, H, nope + k["v"])
    q_pe = _rope(q[..., nope:], inv, cs).astype(h.dtype)
    keys = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe.astype(h.dtype),
                                          (S, H, rd))], -1)
    qs = jnp.concatenate([q[..., :nope], q_pe], -1)
    s = jnp.einsum("qhd,khd->hqk", qs, keys).astype(F32) * sm
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    s = jnp.where(causal[None], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1).astype(h.dtype)
    o = jnp.einsum("hqk,khd->qhd", w, kv[..., nope:]).reshape(S, -1)
    return proj(o, a["wo"], "o")


def _swiglu(mm, h, w1, w3, w2):
    return mm(jax.nn.silu(mm(h, w1)) * mm(h, w3), w2)


def _experts(cfg, mm, h, f):
    """The held experts' share of one expert layer and the shared
    experts; also the top-k expert ids of each position."""
    k = dims(cfg)
    probs = jax.nn.softmax(mm(h, f["router"]).astype(F32), axis=-1)
    topw, topi = jax.lax.top_k(probs, k["K"])
    if cfg["norm_topk_prob"]:
        topw = topw / topw.sum(-1, keepdims=True)
    topw = topw * cfg["routed_scaling_factor"]
    out = _swiglu(mm, h, f["ws1"], f["ws3"], f["ws2"]).astype(F32)
    for j in range(k["Eh"]):
        gate = jnp.sum(jnp.where(topi == k["first"] + j, topw, 0.0), -1)
        y = _swiglu(mm, h, f["we1"][j], f["we3"][j], f["we2"][j])
        out = out + gate[:, None] * y.astype(F32)
    return out.astype(h.dtype), topi


def _layer(cfg, mm, rope, x, lp):
    p, ad = lp
    eps = cfg["rms_norm_eps"]
    x = x + _attention(cfg, mm, _rmsnorm(x, p["ln1"], eps), p["attn"], ad,
                       rope)
    h = _rmsnorm(x, p["ln2"], eps)
    f = p["ffn"]
    if "router" in f:
        y, topi = _experts(cfg, mm, h, f)
        return x + y, topi
    return x + _swiglu(mm, h, f["w1"], f["w3"], f["w2"]), None


@functools.lru_cache(maxsize=None)
def _logits_fn(key: tuple, precision: str, matmul: str):
    cfg = thawed(key)
    dtype = F32 if precision == "float32" else BF16
    mm = _linear(precision)
    nd = cfg["first_k_dense_replace"]

    def run(params, adapter, tokens):
        cast = functools.partial(jax.tree.map, lambda t: t.astype(dtype))
        params, adapter = cast(params), cast(adapter)
        layer = functools.partial(_layer, cfg, mm, yarn(cfg))
        x = params["embed"][tokens]
        x, _ = jax.lax.scan(layer, x, (params["dense_blocks"],
                                       jax.tree.map(lambda t: t[:nd],
                                                    adapter)))
        x, topi = jax.lax.scan(layer, x, (params["blocks"],
                                          jax.tree.map(lambda t: t[nd:],
                                                       adapter)))
        h = _rmsnorm(x, params["ln_f"], cfg["rms_norm_eps"])
        return mm(h, params["lm_head"]).astype(F32), topi

    def at_precision(*args):
        with jax.default_matmul_precision(matmul):
            return run(*args)

    return jax.jit(at_precision)


def logits(cfg: dict, params, adapter, tokens, precision="float32",
           matmul="highest"):
    """(S, V) logits of every position of ``tokens`` (S,) under one
    adapter ``{target: {"A": (L, in, r), "B": (L, r, out)}}``, storing
    in ``precision`` and multiplying at ``matmul`` precision."""
    return routes_and_logits(cfg, params, adapter, tokens, precision,
                             matmul)[0]


def routes_and_logits(cfg: dict, params, adapter, tokens,
                      precision="float32", matmul="highest"):
    """``logits``, and the top-k expert ids (expert layers, S, K) each
    position chose."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    return _logits_fn(frozen(cfg), precision, matmul)(
        params, adapter, jnp.asarray(tokens, jnp.int32))
