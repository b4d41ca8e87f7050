"""Production meshes.

Axes are ``Auto``: the model code shards through ``with_sharding_constraint``
hints and GSPMD propagation, which ``jax.make_mesh``'s default ``Explicit``
axes would turn into sharding-in-types errors.

Kept as functions (never module-level constants) so importing this module
never touches jax device state — the dry-run must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first init.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single pod (256 chips) or 2×16×16 (2 pods, 512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_local_mesh(model_axis: int = 1):
    """Whatever this host has — for tests and examples."""
    n = len(jax.devices())
    assert n % model_axis == 0
    return jax.make_mesh(
        (n // model_axis, model_axis), ("data", "model"),
        axis_types=(AxisType.Auto,) * 2)


def dp_axes(mesh) -> tuple:
    """Data-parallel axes: ('pod','data') on the multi-pod mesh."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))
