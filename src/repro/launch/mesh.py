"""Production mesh definitions.

A function, not a module-level constant — importing this module never
touches jax device state."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many real/fake devices exist (tests)."""
    return _make_mesh((data, model), ("data", "model"))


def dp_axes(mesh) -> tuple:
    """The data-parallel axes of a mesh ('pod' folds into DP)."""
    names = mesh.axis_names
    return tuple(n for n in names if n in ("pod", "data"))


def dp_size(mesh) -> int:
    s = 1
    for n in dp_axes(mesh):
        s *= mesh.shape[n]
    return s
