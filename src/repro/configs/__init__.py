"""Config registry: `get_config(arch_id)` and `get_smoke_config(arch_id)`."""
from __future__ import annotations

from .base import (INPUT_SHAPES, LONG_CONTEXT_WINDOW, EncoderConfig,
                   InputShape, LoRAConfig, MLAConfig, ModelConfig, MoEConfig,
                   RopeScaling, SSMConfig)

_REGISTRY = {}


def register(module_name: str):
    from importlib import import_module
    mod = import_module(f"repro.configs.{module_name}")
    cfg = mod.config()
    _REGISTRY[cfg.name] = mod
    return mod


_ARCH_MODULES = [
    "seamless_m4t_large_v2",
    "qwen2_5_32b",
    "zamba2_7b",
    "llama_3_2_vision_90b",
    "codeqwen1_5_7b",
    "rwkv6_7b",
    "llama4_scout_17b_16e",
    "internlm2_1_8b",
    "deepseek_v2_lite_16b",
    "stablelm_1_6b",
    "llama_7b_paper",
]

for _m in _ARCH_MODULES:
    register(_m)

ARCH_IDS = sorted(_REGISTRY.keys())
ASSIGNED_ARCH_IDS = [a for a in ARCH_IDS if a != "llama-7b-paper"]


def get_config(arch_id: str) -> ModelConfig:
    return _REGISTRY[arch_id].config()


def get_smoke_config(arch_id: str) -> ModelConfig:
    return _REGISTRY[arch_id].reduced()


__all__ = [
    "ModelConfig", "MoEConfig", "MLAConfig", "SSMConfig", "EncoderConfig",
    "LoRAConfig", "RopeScaling", "InputShape", "INPUT_SHAPES",
    "LONG_CONTEXT_WINDOW",
    "ARCH_IDS", "ASSIGNED_ARCH_IDS", "get_config", "get_smoke_config",
]
