"""Architecture config registry: the assigned architectures plus the
SmolLM drafter (+ the paper-scale spec-dec pair) selectable via
``--arch <id>``."""

from __future__ import annotations

import importlib

from repro.configs.shapes import (
    LONG_CONTEXT_OK,
    SHAPES,
    InputShape,
    cache_specs,
    input_specs,
    supports_shape,
)
from repro.models.config import ModelConfig

_MODULES = {
    "whisper-small": "whisper_small",
    "granite-8b": "granite_8b",
    "llama-3.2-vision-11b": "llama_3_2_vision_11b",
    "mamba2-370m": "mamba2_370m",
    "granite-moe-1b-a400m": "granite_moe_1b_a400m",
    "llama3-405b": "llama3_405b",
    "mixtral-8x22b": "mixtral_8x22b",
    "smollm-360m": "smollm_360m",
    "smollm-135m": "smollm_135m",
    "recurrentgemma-2b": "recurrentgemma_2b",
    "granite-34b": "granite_34b",
}

ARCH_NAMES = tuple(_MODULES)


def get_config(name: str) -> ModelConfig:
    if name not in _MODULES:
        raise KeyError(f"unknown arch {name!r}; choose from {ARCH_NAMES}")
    mod = importlib.import_module(f"repro.configs.{_MODULES[name]}")
    return mod.CONFIG


def all_configs() -> dict:
    return {name: get_config(name) for name in ARCH_NAMES}


# Paper-scale speculative decoding pair (target ~= 100M-class llama,
# drafter ~= 20M-class), used by examples and the end-to-end driver.
PAPER_TARGET = ModelConfig(
    name="gls-target-100m", family="dense", num_layers=12, d_model=768,
    num_heads=12, num_kv_heads=4, head_dim=64, d_ff=2048,
    vocab_size=8192, dtype="float32",
)
PAPER_DRAFTER = ModelConfig(
    name="gls-drafter-20m", family="dense", num_layers=4, d_model=384,
    num_heads=6, num_kv_heads=2, head_dim=64, d_ff=1024,
    vocab_size=8192, dtype="float32",
)

__all__ = [
    "ARCH_NAMES",
    "LONG_CONTEXT_OK",
    "PAPER_DRAFTER",
    "PAPER_TARGET",
    "SHAPES",
    "InputShape",
    "all_configs",
    "cache_specs",
    "get_config",
    "input_specs",
    "supports_shape",
]
