"""Architecture registry: 10 assigned architectures (public-literature pool)
plus the 5 MoEs the paper itself evaluates (Table 1). Select with
``--arch <id>``."""

from __future__ import annotations

import importlib
from typing import Dict

from repro.models.config import ModelConfig, InputShape, INPUT_SHAPES

_ASSIGNED = [
    "kimi_k2_1t_a32b",
    "stablelm_1_6b",
    "chatglm3_6b",
    "whisper_large_v3",
    "rwkv6_3b",
    "recurrentgemma_9b",
    "stablelm_3b",
    "minitron_4b",
    "qwen2_vl_7b",
    "deepseek_v2_236b",
]
_PAPER = [
    "mixtral_8x7b",
    "phi_3_5_moe",
    "olmoe_1b_7b",
    "deepseek_moe_16b",
    "qwen15_moe_a2_7b",
]
#: published MoEs the paper does not evaluate, served by the benchmark
_SERVED = [
    "deepseek_v2_lite",
]

ASSIGNED_ARCHS = [m.replace("_", "-") for m in _ASSIGNED]
PAPER_ARCHS = [m.replace("_", "-") for m in _PAPER]
SERVED_ARCHS = [m.replace("_", "-") for m in _SERVED]
ALL_ARCHS = ASSIGNED_ARCHS + PAPER_ARCHS + SERVED_ARCHS

_REGISTRY: Dict[str, ModelConfig] = {}


def get_config(arch: str) -> ModelConfig:
    """Look up an architecture id like 'kimi-k2-1t-a32b'."""
    key = arch.replace("-", "_").replace(".", "_")
    if key not in _REGISTRY:
        mod = importlib.import_module(f"repro.configs.{key}")
        _REGISTRY[key] = mod.CONFIG
    return _REGISTRY[key]


def list_configs():
    return {a: get_config(a) for a in ALL_ARCHS}
