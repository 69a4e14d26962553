"""Architecture config registry: ``get_config("<arch-id>")``."""
from __future__ import annotations

import importlib

from repro.configs.base import (ALL_SHAPES, LONG_CONTEXT_ARCHS, SHAPES_BY_NAME,
                                MLACfg, ModelConfig, MoECfg, ShapeSpec, SSMCfg,
                                YaRNCfg, shapes_for)

_MODULES = {
    "qwen1.5-32b": "qwen1_5_32b",
    "llama3.2-1b": "llama3_2_1b",
    "internlm2-1.8b": "internlm2_1_8b",
    "gemma2-27b": "gemma2_27b",
    "deepseek-v2-236b": "deepseek_v2_236b",
    "deepseek-moe-16b": "deepseek_moe_16b",
    "deepseek-v2-lite": "deepseek_v2_lite",
    "whisper-large-v3": "whisper_large_v3",
    "llama-3.2-vision-11b": "llama3_2_vision_11b",
    "hymba-1.5b": "hymba_1_5b",
    "xlstm-350m": "xlstm_350m",
    "vit-base-16": "vit_base",
}

ASSIGNED_ARCHS = tuple(k for k in _MODULES if k != "vit-base-16")


def get_config(name: str) -> ModelConfig:
    if name not in _MODULES:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(_MODULES)}")
    mod = importlib.import_module(f"repro.configs.{_MODULES[name]}")
    return mod.CONFIG


__all__ = ["get_config", "ASSIGNED_ARCHS", "ALL_SHAPES", "SHAPES_BY_NAME",
           "ModelConfig", "MoECfg", "MLACfg", "SSMCfg", "ShapeSpec", "YaRNCfg",
           "shapes_for", "LONG_CONTEXT_ARCHS"]
