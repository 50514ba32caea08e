"""Config registry: ``get_config("<arch-id>")`` for every assigned arch."""
from __future__ import annotations

import importlib
from typing import Dict, List

from repro.configs.base import (  # noqa: F401  (re-exported)
    ArchConfig, MoEConfig, MLAConfig, SSMConfig, RGLRUConfig, ShapeConfig,
    INPUT_SHAPES, YarnConfig, pad_vocab,
)

_MODULES = {
    "internvl2-1b": "repro.configs.internvl2_1b",
    "mamba2-780m": "repro.configs.mamba2_780m",
    "deepseek-v2-lite-16b": "repro.configs.deepseek_v2_lite_16b",
    "dbrx-132b": "repro.configs.dbrx_132b",
    "command-r-35b": "repro.configs.command_r_35b",
    "qwen3-14b": "repro.configs.qwen3_14b",
    "smollm-360m": "repro.configs.smollm_360m",
    "musicgen-large": "repro.configs.musicgen_large",
    "gemma3-4b": "repro.configs.gemma3_4b",
    "recurrentgemma-2b": "repro.configs.recurrentgemma_2b",
}

ARCH_IDS: List[str] = list(_MODULES)


def get_config(name: str) -> ArchConfig:
    if name.endswith("-smoke"):
        return get_config(name[: -len("-smoke")]).reduced()
    if name not in _MODULES:
        raise KeyError(f"unknown arch {name!r}; known: {ARCH_IDS}")
    return importlib.import_module(_MODULES[name]).CONFIG


def all_configs() -> Dict[str, ArchConfig]:
    return {k: get_config(k) for k in ARCH_IDS}
