"""Architecture config registry.

Each ``<arch>.py`` exposes ``config() -> ModelConfig`` (the exact assigned
configuration) and ``smoke() -> ModelConfig`` (a reduced same-family
variant: <=2 pattern groups, d_model<=512, <=4 experts) used by CPU smoke
tests.  Full configs are exercised only via the AOT dry-run.
"""
from __future__ import annotations

import importlib

ASSIGNED_ARCHS = [
    "qwen3-moe-235b-a22b",
    "gemma-2b",
    "whisper-base",
    "jamba-v0.1-52b",
    "mamba2-1.3b",
    "pixtral-12b",
    "qwen3-8b",
    "qwen2-moe-a2.7b",
    "moonlight-16b-a3b",
    "nemotron-4-340b",
]

PAPER_CONFIGS = ["dipaco-150m", "dipaco-dense-1b"]

ALL_CONFIGS = ASSIGNED_ARCHS + PAPER_CONFIGS


def _module(name: str):
    mod = name.replace("-", "_").replace(".", "_")
    return importlib.import_module(f"repro.configs.{mod}")


def get_config(name: str):
    if name not in ALL_CONFIGS:
        raise KeyError(f"unknown arch {name!r}; known: {ALL_CONFIGS}")
    return _module(name).config()


def get_smoke_config(name: str):
    if name not in ALL_CONFIGS:
        raise KeyError(f"unknown arch {name!r}; known: {ALL_CONFIGS}")
    return _module(name).smoke()
