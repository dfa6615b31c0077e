"""Production mesh construction (assignment spec).

``make_production_mesh`` is a function (not a module-level constant) so that
importing this module never touches jax device state.

Every mesh is built with ``Auto`` axes: the sharding code places arrays
with ``with_sharding_constraint`` and ``NamedSharding`` and lets the
partitioner propagate the rest, which ``jax.make_mesh``'s default
``Explicit`` axes refuse.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_host_mesh", "make_mesh"]


def make_mesh(shape: tuple, axes: tuple, *, devices=None):
    """``jax.make_mesh`` with ``Auto`` axes (optionally over ``devices``)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod ("data", "model"); 2 pods = 512 chips
    multi-pod ("pod", "data", "model")."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small ("data", "model") mesh over the first ``data * model`` devices
    — used by tests, the weak-scaling benchmark and the four-chip smoke
    phase."""
    return make_mesh((data, model), ("data", "model"),
                     devices=jax.devices()[:data * model])
