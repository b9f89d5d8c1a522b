"""Production mesh construction (see MULTI-POD DRY-RUN spec).

A function, not a module-level constant: importing this module never touches
jax device state.
"""

from __future__ import annotations

from jax import make_mesh
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def dp_axes(multi_pod: bool) -> tuple[str, ...]:
    """Axes used for data parallelism (batch sharding)."""
    return ("pod", "data") if multi_pod else ("data",)


def make_local_mesh(n_data: int = 1, n_model: int = 1):
    """Small mesh over however many (host) devices exist — tests only."""
    return make_mesh((n_data, n_model), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
