"""What every architecture's counts share: the bytes of each dtype, the
parameters of one adapter, and the roofline bound. The counts of a
block's work are the architecture module's (``chipbench/arch``).
"""
from __future__ import annotations

DTYPE_BYTES = {"float32": 4, "bfloat16": 2}


def adapter_params(target_dims, cfg: dict, rank: int) -> int:
    """A and B of one adapter, all targets, all layers; ``target_dims``
    is the architecture module's."""
    return cfg["n_layers"] * sum(rank * sum(target_dims(cfg, t))
                                 for t in cfg["lora_targets"])


def least_seconds(flops: int, nbytes: int, peak: dict) -> float:
    """The roofline bound: the larger of compute time and memory time."""
    return max(flops / peak["flops"], nbytes / peak["hbm_bw"])
