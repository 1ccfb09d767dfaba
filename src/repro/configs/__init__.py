"""Architecture config registry.

``get(name)`` -> full published config (used only by the dry-run, via
ShapeDtypeStructs — never allocated on CPU).
``get_smoke(name)`` -> reduced same-family config for CPU smoke tests.
"""
from __future__ import annotations

import importlib

from repro.configs.base import (  # noqa: F401  (re-exports)
    MLAConfig,
    ModelConfig,
    MoEConfig,
    SHAPES,
    SSMConfig,
    ShapeSpec,
    LONG_CONTEXT_OK,
    cell_applicable,
)

ARCHS = [
    "granite-3-2b",
    "minitron-4b",
    "gemma-2b",
    "qwen3-14b",
    "falcon-mamba-7b",
    "deepseek-v3-671b",
    "mixtral-8x7b",
    "zamba2-7b",
    "seamless-m4t-large-v2",
    "llava-next-mistral-7b",
]

# extra (non-assigned) configs: the paper-scale end-to-end example model and
# the MoE the benchmark records an expert-specialised fine-tune of
EXTRA = ["florbench-100m", "moonlight-16b-a3b"]


def _module(name: str):
    return importlib.import_module("repro.configs." + name.replace("-", "_"))


def get(name: str) -> ModelConfig:
    if name not in ARCHS + EXTRA:
        raise KeyError(f"unknown arch {name!r}; known: {ARCHS + EXTRA}")
    return _module(name).CONFIG


def get_smoke(name: str) -> ModelConfig:
    if name not in ARCHS + EXTRA:
        raise KeyError(f"unknown arch {name!r}; known: {ARCHS + EXTRA}")
    return _module(name).SMOKE


def list_archs() -> list[str]:
    return list(ARCHS)
