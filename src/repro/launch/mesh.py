"""Mesh construction. Importing this module never touches jax device state."""

from __future__ import annotations

import math

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """The production mesh: one v5e pod (16x16 = 256 chips) or two pods
    (2x16x16 = 512 chips; the leading 'pod' axis is the DCN-connected
    data-parallel axis)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """jax.make_mesh, tolerant of a device pool larger than the mesh."""
    n = math.prod(shape)
    devices = jax.devices()
    if len(devices) < n:
        raise ValueError(
            f"mesh {shape} needs {n} devices, have {len(devices)} "
            "(dry-runs must set --xla_force_host_platform_device_count)"
        )
    # Auto axes: the model code shards through with_sharding_constraint,
    # which rejects specs on the Explicit axes jax.make_mesh defaults to.
    return jax.make_mesh(
        shape, axes, devices=devices[:n], axis_types=(AxisType.Auto,) * len(shape)
    )


def make_test_mesh(data: int = 2, model: int = 2, pod: int | None = None):
    """Small mesh for unit tests (requires forced host device count)."""
    if pod:
        return make_mesh((pod, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))
