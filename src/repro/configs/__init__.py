"""Registry of assigned architectures (``--arch <id>``)."""
from __future__ import annotations

from repro.configs.base import ArchConfig, ShapeConfig, SHAPES, cell_is_runnable

from repro.configs.gemma3_27b import CONFIG as _gemma3
from repro.configs.starcoder2_15b import CONFIG as _starcoder2
from repro.configs.command_r_plus_104b import CONFIG as _command_r
from repro.configs.yi_9b import CONFIG as _yi
from repro.configs.zamba2_2p7b import CONFIG as _zamba2
from repro.configs.paligemma_3b import CONFIG as _paligemma
from repro.configs.falcon_mamba_7b import CONFIG as _falcon_mamba
from repro.configs.hubert_xlarge import CONFIG as _hubert
from repro.configs.qwen3_moe_30b_a3b import CONFIG as _qwen3
from repro.configs.arctic_480b import CONFIG as _arctic
from repro.configs.granite_4_0_h_micro import CONFIG as _granite_h_micro

ARCHS: dict[str, ArchConfig] = {
    c.name: c for c in [
        _gemma3, _starcoder2, _command_r, _yi, _zamba2,
        _paligemma, _falcon_mamba, _hubert, _qwen3, _arctic,
        _granite_h_micro,
    ]
}


def get_arch(name: str) -> ArchConfig:
    if name not in ARCHS:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(ARCHS)}")
    return ARCHS[name]


def get_shape(name: str) -> ShapeConfig:
    if name not in SHAPES:
        raise KeyError(f"unknown shape {name!r}; known: {sorted(SHAPES)}")
    return SHAPES[name]


def all_cells():
    """All (arch, shape, runnable, reason) assignment cells (arch x shape)."""
    out = []
    for a in ARCHS.values():
        for s in SHAPES.values():
            ok, why = cell_is_runnable(a, s)
            out.append((a, s, ok, why))
    return out
