"""Distribution layer: logical-axis sharding rules."""

from repro.dist.sharding import (
    DEFAULT_RULES,
    MULTIPOD_RULES,
    MULTIPOD_SERVE_RULES,
    SERVE_RULES,
    axis_rules,
    current_rules,
    fit_spec_to_shape,
    sanitize_shardings,
    shard,
    spec_for,
)

__all__ = [
    "DEFAULT_RULES",
    "MULTIPOD_RULES",
    "MULTIPOD_SERVE_RULES",
    "SERVE_RULES",
    "axis_rules",
    "current_rules",
    "fit_spec_to_shape",
    "sanitize_shardings",
    "shard",
    "spec_for",
]
