from repro.configs.base import (
    ARCH_IDS,
    SHAPES,
    MambaConfig,
    ModelConfig,
    MoEConfig,
    ShapeCell,
    all_configs,
    cells_for,
    cut_config,
    get_config,
    reduced_config,
)

__all__ = [
    "ARCH_IDS", "SHAPES", "MambaConfig", "ModelConfig", "MoEConfig",
    "ShapeCell", "all_configs", "cells_for", "cut_config", "get_config",
    "reduced_config",
]
