"""Assigned-architecture registry: ``get_config(arch_id)`` / ``list_archs()``."""
from __future__ import annotations

import importlib
from typing import Dict, List

from repro.configs.base import (  # noqa: F401
    ModelConfig,
    ShapeConfig,
    SHAPES,
    get_shape,
    shape_applicable,
)

_ARCH_MODULES: Dict[str, str] = {
    "internvl2-76b": "repro.configs.internvl2_76b",
    "mixtral-8x22b": "repro.configs.mixtral_8x22b",
    "granite-moe-3b-a800m": "repro.configs.granite_moe_3b_a800m",
    "command-r-35b": "repro.configs.command_r_35b",
    "starcoder2-15b": "repro.configs.starcoder2_15b",
    "phi3-medium-14b": "repro.configs.phi3_medium_14b",
    "qwen1.5-110b": "repro.configs.qwen15_110b",
    "recurrentgemma-9b": "repro.configs.recurrentgemma_9b",
    "mamba2-1.3b": "repro.configs.mamba2_13b",
    "whisper-tiny": "repro.configs.whisper_tiny",
    "granite-4.0-h-small": "repro.configs.granite4h_small",
    "granite-4.0-h-small-ep8": "repro.configs.granite4h_small_ep8",
}


def list_archs() -> List[str]:
    return list(_ARCH_MODULES.keys())


def get_config(arch_id: str, reduced: bool = False) -> ModelConfig:
    if arch_id not in _ARCH_MODULES:
        raise KeyError(f"unknown arch {arch_id!r}; known: {list_archs()}")
    mod = importlib.import_module(_ARCH_MODULES[arch_id])
    return mod.reduced() if reduced else mod.CONFIG
