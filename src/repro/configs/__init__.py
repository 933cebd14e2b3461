"""Assigned architecture configs (one module per arch) + shape registry.

``get_config(name)`` returns the full published config;
``get_smoke_config(name)`` returns a reduced same-family config for CPU
smoke tests (small widths/layers/experts, same structural features);
``get_one_chip_config(name)`` the published widths cut to one TPU v5e chip
(only archs whose module defines ``ONE_CHIP_CONFIG``).
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Dict, List

from ..models.common import ModelConfig

ARCH_IDS = [
    "chameleon_34b", "recurrentgemma_2b", "gemma3_4b", "qwen3_14b", "yi_6b",
    "nemotron_4_15b", "mamba2_370m", "qwen3_moe_235b_a22b", "kimi_k2_1t_a32b",
    "whisper_base",
]


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq_len: int
    global_batch: int
    step: str            # train | prefill | decode


SHAPES: Dict[str, ShapeSpec] = {
    "train_4k": ShapeSpec("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524288, 1, "decode"),
}


def _module(name: str):
    return importlib.import_module(f".{name.replace('-', '_')}", __package__)


def get_config(name: str) -> ModelConfig:
    return _module(name).CONFIG


def get_smoke_config(name: str) -> ModelConfig:
    return _module(name).SMOKE_CONFIG


def get_one_chip_config(name: str) -> ModelConfig:
    mod = _module(name)
    if not hasattr(mod, "ONE_CHIP_CONFIG"):
        raise ValueError(f"{name} has no one-chip configuration")
    return mod.ONE_CHIP_CONFIG


def shape_cells(arch: str) -> List[str]:
    """The shapes this arch runs (skips documented in DESIGN.md §4)."""
    cfg = get_config(arch)
    cells = ["train_4k", "prefill_32k", "decode_32k"]
    if cfg.sub_quadratic:
        cells.append("long_500k")
    return cells


def all_cells() -> List[tuple]:
    return [(a, s) for a in ARCH_IDS for s in shape_cells(a)]
