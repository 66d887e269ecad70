"""Production mesh definition (functions only — importing this module
never touches jax device state)."""
from __future__ import annotations

import jax

__all__ = ["make_mesh_compat", "make_production_mesh", "make_dp_mesh",
           "dp_axes"]


def make_mesh_compat(shape, axes):
    """``jax.make_mesh`` with every axis Auto (sharding propagation
    decides placements, as the model code expects)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 (data, model) per pod; 2x16x16 (pod, data, model) multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh_compat(shape, axes)


def make_dp_mesh(n_devices: int | None = None):
    """Pure data-parallel mesh (gradient-compression study / examples)."""
    n = n_devices or len(jax.devices())
    return make_mesh_compat((n,), ("data",))


def dp_axes(mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
