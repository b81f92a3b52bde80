from repro.configs.base import (
    SHAPES,
    ArchConfig,
    ShapeConfig,
    all_archs,
    all_cells,
    applicable,
    get_arch,
    get_shape,
    run_arch,
)

__all__ = [
    "SHAPES",
    "ArchConfig",
    "ShapeConfig",
    "all_archs",
    "all_cells",
    "applicable",
    "get_arch",
    "get_shape",
    "run_arch",
]
