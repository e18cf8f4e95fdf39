"""Assigned-architecture registry: one module per architecture, each with
``config()`` (exact published dims) and ``smoke_config()`` (reduced,
same family structure, CPU-runnable). A configuration cut to fit one chip
has a module of its own beside the published one (``zamba2_7b_l12``),
registered by alias and left out of ``ARCHITECTURES``."""
from __future__ import annotations

import importlib
from typing import Dict, List

from ..models.config import ModelConfig

ARCHITECTURES: List[str] = [
    "seamless_m4t_large_v2",
    "yi_6b",
    "gemma_2b",
    "glm4_9b",
    "gemma3_4b",
    "zamba2_1p2b",
    "zamba2_7b",
    "granite_moe_3b_a800m",
    "deepseek_v2_lite_16b",
    "mamba2_370m",
    "llama_3p2_vision_90b",
]

_ALIASES = {
    "seamless-m4t-large-v2": "seamless_m4t_large_v2",
    "yi-6b": "yi_6b",
    "gemma-2b": "gemma_2b",
    "glm4-9b": "glm4_9b",
    "gemma3-4b": "gemma3_4b",
    "zamba2-1.2b": "zamba2_1p2b",
    "zamba2-7b": "zamba2_7b",
    "zamba2-7b-l12": "zamba2_7b_l12",
    "granite-moe-3b-a800m": "granite_moe_3b_a800m",
    "deepseek-v2-lite-16b": "deepseek_v2_lite_16b",
    "mamba2-370m": "mamba2_370m",
    "llama-3.2-vision-90b": "llama_3p2_vision_90b",
}


def canonical(name: str) -> str:
    return _ALIASES.get(name, name.replace("-", "_").replace(".", "p"))


def get_config(name: str, smoke: bool = False) -> ModelConfig:
    mod = importlib.import_module(f".{canonical(name)}", __package__)
    return mod.smoke_config() if smoke else mod.config()


def all_configs(smoke: bool = False) -> Dict[str, ModelConfig]:
    return {a: get_config(a, smoke=smoke) for a in ARCHITECTURES}
