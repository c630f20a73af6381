"""Architecture registry: one module per assigned arch (+ the paper's own
ViT edge catalog).  ``get_config(name)`` returns the full production config,
``get_smoke(name)`` a reduced same-family config for CPU tests.
"""
from __future__ import annotations

import importlib

ARCH_IDS = [
    "zamba2-1.2b", "stablelm-12b", "chatglm3-6b", "qwen1.5-0.5b",
    "qwen3-14b", "pixtral-12b", "mixtral-8x22b", "mixtral-8x7b",
    "whisper-small", "xlstm-125m", "moonlight-16b-a3b",
]

_MOD = {a: a.replace("-", "_").replace(".", "_") for a in ARCH_IDS}


def _load(name: str):
    if name not in _MOD:
        raise KeyError(f"unknown arch {name!r}; have {ARCH_IDS}")
    return importlib.import_module(f"repro.configs.{_MOD[name]}")


def get_config(name: str):
    return _load(name).CONFIG


def get_smoke(name: str):
    return _load(name).SMOKE
