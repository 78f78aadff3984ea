"""Architecture registry: arch-id -> ModelConfig, plus per-shape adjustments."""
from __future__ import annotations

import dataclasses
import importlib

from repro.models.config import INPUT_SHAPES, InputShape, ModelConfig

_MODULES = {
    "qwen1.5-0.5b": "qwen1_5_0_5b",
    "mamba2-370m": "mamba2_370m",
    "musicgen-large": "musicgen_large",
    "chameleon-34b": "chameleon_34b",
    "minitron-4b": "minitron_4b",
    "deepseek-moe-16b": "deepseek_moe_16b",
    "deepseek-v3-671b": "deepseek_v3_671b",
    "deepseek-v2-lite": "deepseek_v2_lite",
    "zamba2-7b": "zamba2_7b",
    "stablelm-1.6b": "stablelm_1_6b",
    "qwen1.5-32b": "qwen1_5_32b",
}

ARCH_IDS = tuple(_MODULES)

# sliding window used by attention archs for long_500k (DESIGN.md §4)
LONG_CONTEXT_WINDOW = 4096


def get_config(arch_id: str) -> ModelConfig:
    if arch_id not in _MODULES:
        raise ValueError(f"unknown arch {arch_id!r}; have {sorted(_MODULES)}")
    mod = importlib.import_module(f"repro.configs.{_MODULES[arch_id]}")
    return mod.CONFIG


def uses_attention(cfg: ModelConfig) -> bool:
    return cfg.family != "ssm"


def for_shape(cfg: ModelConfig, shape: InputShape) -> ModelConfig:
    """Shape-specific adjustments: long_500k on attention archs -> SWA window."""
    if shape.name == "long_500k" and uses_attention(cfg):
        return dataclasses.replace(cfg, sliding_window=LONG_CONTEXT_WINDOW)
    return cfg


def cache_len(cfg: ModelConfig, shape: InputShape) -> int:
    """KV-cache length for decode shapes (window for SWA)."""
    if cfg.sliding_window is not None:
        return min(cfg.sliding_window, shape.seq_len)
    return shape.seq_len


def shape_by_name(name: str) -> InputShape:
    return INPUT_SHAPES[name]
