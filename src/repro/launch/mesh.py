"""Production mesh construction.

A FUNCTION, not a module-level constant — importing this module never
touches jax device state (required so smoke tests keep a 1-device
platform; only launch/dryrun.py forces 512 host devices)."""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axis types on every axis."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(n_data: int = 2, n_model: int = 2):
    """Small mesh for CI-style distributed tests (host device count)."""
    return make_mesh((n_data, n_model), ("data", "model"))


def dp_axes(mesh) -> tuple[str, ...]:
    """The data-parallel axes of a mesh (includes 'pod' when present)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))
