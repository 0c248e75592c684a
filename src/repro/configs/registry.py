"""Architecture registry: ``--arch <id>`` resolution."""
from __future__ import annotations

import importlib

from repro.configs.base import ArchConfig, SHAPES

_ARCH_MODULES = {
    "nemotron-4-15b": "repro.configs.nemotron_4_15b",
    "minitron-4b": "repro.configs.minitron_4b",
    "chatglm3-6b": "repro.configs.chatglm3_6b",
    "granite-20b": "repro.configs.granite_20b",
    "granite-moe-3b-a800m": "repro.configs.granite_moe_3b",
    "olmoe-1b-7b": "repro.configs.olmoe_1b_7b",
    "seamless-m4t-large-v2": "repro.configs.seamless_m4t_large",
    "chameleon-34b": "repro.configs.chameleon_34b",
    "mamba2-130m": "repro.configs.mamba2_130m",
    "jamba-v0.1-52b": "repro.configs.jamba_52b",
    "granite-4.0-h-small": "repro.configs.granite_4h_small",
}

ARCH_IDS = list(_ARCH_MODULES)


def get_config(arch_id: str, smoke: bool = False) -> ArchConfig:
    if arch_id not in _ARCH_MODULES:
        raise KeyError(f"unknown arch {arch_id!r}; known: {ARCH_IDS}")
    mod = importlib.import_module(_ARCH_MODULES[arch_id])
    return mod.SMOKE if smoke else mod.CONFIG


def all_cells() -> list[tuple[str, str]]:
    """Every (arch x shape) cell, inapplicable ones included (caller filters)."""
    return [(a, s) for a in ARCH_IDS for s in SHAPES]
