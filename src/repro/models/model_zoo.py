"""Uniform model construction: ``build_model(cfg)`` -> family implementation.

All families expose the same API (see transformer.py docstring):
param_specs / init_params / loss_fn / prefill / decode_step /
input_specs / cache_specs.
"""
from __future__ import annotations

from repro.configs.base import ModelConfig
from repro.models.encdec import EncDecLM
from repro.models.granite_hybrid import GraniteHybridLM
from repro.models.mamba2 import Mamba2LM
from repro.models.rglru import GriffinLM
from repro.models.transformer import TransformerLM


def build_model(cfg: ModelConfig, remat_policy: str = "full"):
    if cfg.family in ("dense", "moe", "vlm"):
        return TransformerLM(cfg, remat_policy)
    if cfg.family == "ssm":
        return Mamba2LM(cfg, remat_policy)
    if cfg.family == "hybrid":
        return GriffinLM(cfg, remat_policy)
    if cfg.family == "hybrid_moe":
        return GraniteHybridLM(cfg, remat_policy)
    if cfg.family == "encdec":
        return EncDecLM(cfg, remat_policy)
    raise ValueError(f"unknown family {cfg.family!r}")
