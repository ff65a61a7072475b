"""Operations and bytes the algorithm needs, from the configuration's
shapes. They count the work, not what the program happens to do: the
KV of the live context (not the whole cache), each distinct adapter of
a batch at its true rank (not the padded bank), and the weights at the
configuration's dtype once per step.

All counts are for one engine step. A multiply-add is two operations.
"""
from __future__ import annotations

from typing import Iterable, Tuple

from .weights import dims, target_dims

DTYPE_BYTES = {"float32": 4, "bfloat16": 2}


def _itemsize(cfg: dict, part: str) -> int:
    return DTYPE_BYTES[cfg["precision"][part]]


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


def adapter_params(cfg: dict, rank: int) -> int:
    """A and B of one adapter, all targets, all layers."""
    return cfg["n_layers"] * sum(rank * sum(target_dims(cfg, t))
                                 for t in cfg["lora_targets"])


def kv_bytes_per_token(cfg: dict) -> int:
    k = dims(cfg)
    return 2 * k["L"] * k["kv"] * _itemsize(cfg, "kv_cache")


def token_flops(cfg: dict, rank: int, context: int) -> int:
    """One token through every layer: base matmuls, the LoRA delta at
    ``rank``, and attention over ``context`` keys (QK^T and PV)."""
    k = dims(cfg)
    lora = rank * sum(sum(target_dims(cfg, t)) for t in cfg["lora_targets"])
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
              + sum(adapter_params(cfg, r) for r in adapters.values())
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
        lora = r * sum(sum(target_dims(cfg, t))
                       for t in cfg["lora_targets"])
        flops += 2 * k["L"] * s * (block_matmul_params(cfg) + lora)
        flops += 2 * k["L"] * 2 * k["q"] * s * (s + 1) // 2
        flops += 2 * k["d"] * k["V"]
    return flops


def least_seconds(flops: int, nbytes: int, peak: dict) -> float:
    """The roofline bound: the larger of compute time and memory time."""
    return max(flops / peak["flops"], nbytes / peak["hbm_bw"])
