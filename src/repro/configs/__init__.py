"""Config registry: ``--arch <id>`` resolution."""
from __future__ import annotations

import importlib

_ARCH_MODULES = {
    "qwen2.5-3b": "repro.configs.qwen25_3b",
    "qwen2-vl-2b": "repro.configs.qwen2_vl_2b",
    "h2o-danube-1.8b": "repro.configs.h2o_danube_1p8b",
    "mamba2-780m": "repro.configs.mamba2_780m",
    "jamba-v0.1-52b": "repro.configs.jamba_v01_52b",
    "qwen3-moe-30b-a3b": "repro.configs.qwen3_moe_30b_a3b",
    "gemma-2b": "repro.configs.gemma_2b",
    "dbrx-132b": "repro.configs.dbrx_132b",
    "whisper-base": "repro.configs.whisper_base",
    "qwen2.5-14b": "repro.configs.qwen25_14b",
    "deepseek-v2-lite": "repro.configs.deepseek_v2_lite",
}

ARCH_IDS = tuple(_ARCH_MODULES)


def get_config(arch_id: str):
    if arch_id not in _ARCH_MODULES:
        raise KeyError(f"unknown arch {arch_id!r}; known: {sorted(ARCH_IDS)}")
    return importlib.import_module(_ARCH_MODULES[arch_id]).CONFIG


def get_smoke_config(arch_id: str):
    return importlib.import_module(_ARCH_MODULES[arch_id]).smoke_config()
