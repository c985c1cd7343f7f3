"""Architecture registry: `get_config(arch_id)` / `get_smoke_config(arch_id)`.

One module per assigned architecture (exact published config) plus the
paper's own evaluation model (llama2-7b). Smoke configs are reduced
same-family variants for CPU tests.
"""
from __future__ import annotations

import importlib
from typing import Dict, List

from repro.models.common import ModelConfig

ARCH_IDS: List[str] = [
    "minitron_4b",
    "qwen3_0_6b",
    "llama3_8b",
    "qwen2_72b",
    "whisper_medium",
    "xlstm_125m",
    "deepseek_v2_lite",
    "mixtral_8x22b",
    "recurrentgemma_2b",
    "llama_3_2_vision_11b",
    # the paper's own model (Tbl. III-X)
    "llama2_7b",
]


def _norm(arch: str) -> str:
    return arch.replace("-", "_").replace(".", "_")


def get_config(arch: str) -> ModelConfig:
    mod = importlib.import_module(f"repro.configs.{_norm(arch)}")
    return mod.CONFIG


def get_smoke_config(arch: str) -> ModelConfig:
    mod = importlib.import_module(f"repro.configs.{_norm(arch)}")
    return mod.SMOKE


def all_configs() -> Dict[str, ModelConfig]:
    return {a: get_config(a) for a in ARCH_IDS}
