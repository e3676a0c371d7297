"""Production mesh definitions.

Single pod : (16, 16)    = 256 chips, axes (data, model)
Multi-pod  : (2, 16, 16) = 512 chips, axes (pod, data, model)

The `model` axis carries TP/EP (and graph parallelism for the ANN engine —
the paper's linear-scaling strategy, §6.3); `data`/`pod` carry DP/FSDP and
query parallelism. Functions, not module constants: importing this module
never touches jax device state.
"""

from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_mesh", "dp_axes", "mesh_shape",
           "enter_mesh"]


def make_mesh(shape, axes):
    """jax.make_mesh with Auto axis_types."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def enter_mesh(mesh):
    """Context manager activating `mesh` (jax.set_mesh)."""
    return jax.set_mesh(mesh)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def dp_axes(mesh) -> tuple[str, ...]:
    """Axes usable for batch/data parallelism."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def mesh_shape(mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))
