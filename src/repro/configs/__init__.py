"""Architecture registry: --arch <id> resolution for the 11
architectures (exact configs from public literature; see each module's
docstring for the source tier)."""

from __future__ import annotations

import importlib

from ..models.config import ArchConfig, SHAPES, ShapeConfig

_MODULES = {
    "qwen3-0.6b": "qwen3_0_6b",
    "qwen2.5-14b": "qwen2_5_14b",
    "granite-8b": "granite_8b",
    "h2o-danube-3-4b": "h2o_danube3_4b",
    "qwen2-moe-a2.7b": "qwen2_moe_a2_7b",
    "olmoe-1b-7b": "olmoe_1b_7b",
    "jamba-v0.1-52b": "jamba_v0_1_52b",
    "internvl2-2b": "internvl2_2b",
    "whisper-tiny": "whisper_tiny",
    "mamba2-370m": "mamba2_370m",
    "granite-4.0-h-small": "granite_4_0_h_small",
}

ARCH_IDS = tuple(_MODULES)


def _mod(arch: str):
    key = arch.lower().replace("_", "-")
    if key not in _MODULES:
        raise KeyError(f"unknown arch {arch!r}; known: {', '.join(ARCH_IDS)}")
    return importlib.import_module(f".{_MODULES[key]}", __package__)


def get_config(arch: str) -> ArchConfig:
    return _mod(arch).CONFIG


def get_reduced(arch: str) -> ArchConfig:
    return _mod(arch).reduced()


def cells(include_skipped: bool = False
          ) -> list[tuple[str, ShapeConfig, bool]]:
    """Every (arch, shape) cell with a runnable flag.  long_500k is
    skipped for pure full-attention archs (sub-quadratic requirement,
    DESIGN.md §Arch-applicability)."""
    out = []
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        for shape in SHAPES.values():
            runnable = True
            if shape.name == "long_500k" and not cfg.supports_long_context():
                runnable = False
            if runnable or include_skipped:
                out.append((arch, shape, runnable))
    return out


__all__ = ["ARCH_IDS", "SHAPES", "cells", "get_config", "get_reduced"]
