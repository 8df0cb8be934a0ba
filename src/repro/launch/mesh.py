"""Production mesh builders. Functions, not module constants — importing
this module never touches jax device state."""
from __future__ import annotations

import jax


def make_mesh(shape, axes, *, axis_types=None):
    """``jax.make_mesh`` with every axis ``Auto`` unless told otherwise —
    the only axis type the repo's shard_map steps are written for."""
    if axis_types is None:
        axis_types = (jax.sharding.AxisType.Auto,) * len(axes)
    return jax.make_mesh(shape, axes, axis_types=axis_types)


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips (data, model).
    Multi-pod: 2x16x16 = 512 chips (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def batch_axes(mesh):
    """The data-parallel axes of a production mesh."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def all_axes(mesh):
    return tuple(mesh.axis_names)
