"""Mesh construction (the contract used by the engine and the dry-run).

Every mesh in the repo is built here with ``AxisType.Auto`` axes.  The
installed JAX defaults ``jax.make_mesh`` to ``Explicit`` axes, which put
the mesh axis into the *type* of every array a shard_map returns; such
arrays then refuse to mix with unsharded ones under ``vmap`` (the SPMD
engine's cached states meet the logical engine's repair path at
``commit()``) and turn ``with_sharding_constraint`` into an assert.

Functions only, so importing this module never touches device state.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_mesh", "make_production_mesh"]


def make_mesh(axis_shapes, axis_names, devices=None):
    """``jax.make_mesh`` with ``Auto`` axes (see module docstring)."""
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=(AxisType.Auto,) * len(axis_shapes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """(16, 16) data x model single pod; (2, 16, 16) pod x data x model for
    the two-pod (512-chip) configuration."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
