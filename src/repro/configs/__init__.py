"""Architecture registry (``--arch <id>`` resolution).

Mirrors gem5-resources' "known-good configurations": each module in this
package exports one ``CONFIG`` with the exact published numbers.
"""

from __future__ import annotations

from typing import Dict, List

from repro.configs.base import (  # noqa: F401
    ArchConfig, ShapeConfig, SHAPES, SUBQUADRATIC, cell_runnable, smoke,
    smoke_shape,
)

from repro.configs.olmoe_1b_7b import CONFIG as _olmoe
from repro.configs.mixtral_8x22b import CONFIG as _mixtral
from repro.configs.stablelm_1_6b import CONFIG as _stablelm
from repro.configs.deepseek_67b import CONFIG as _deepseek
from repro.configs.minicpm_2b import CONFIG as _minicpm
from repro.configs.nemotron_4_15b import CONFIG as _nemotron
from repro.configs.qwen2_vl_7b import CONFIG as _qwen2vl
from repro.configs.rwkv6_7b import CONFIG as _rwkv6
from repro.configs.jamba_v0_1_52b import CONFIG as _jamba
from repro.configs.whisper_small import CONFIG as _whisper
from repro.configs.granite_4_0_h_micro import CONFIG as _granite

REGISTRY: Dict[str, ArchConfig] = {
    c.name: c for c in (
        _olmoe, _mixtral, _stablelm, _deepseek, _minicpm, _nemotron,
        _qwen2vl, _rwkv6, _jamba, _whisper, _granite,
    )
}


def get_config(name: str) -> ArchConfig:
    try:
        return REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown arch {name!r}; one of {sorted(REGISTRY)}") from None


def all_archs() -> List[str]:
    return sorted(REGISTRY)


def get_shape(name: str) -> ShapeConfig:
    try:
        return SHAPES[name]
    except KeyError:
        raise KeyError(
            f"unknown shape {name!r}; one of {sorted(SHAPES)}") from None
