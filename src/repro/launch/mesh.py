"""Production mesh construction.

Functions, not module-level constants: importing this module never touches
jax device state.  The production target is TPU v5e: one pod = 16x16 = 256
chips on ICI; the multi-pod mesh adds the DCN "pod" axis (the paper's slow
inter-server network).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import AxisType, Mesh

__all__ = ["make_production_mesh", "make_mesh", "dp_axes", "slow_axis"]


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    """Mesh over the first prod(shape) devices (works on subsets, so small
    test meshes can be carved out of the 512 dry-run host devices)."""
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise ValueError(
            f"need {n} devices for mesh {shape}, have {len(devices)}")
    axis_types = (AxisType.Auto,) * len(axes)
    if len(devices) == n:
        return jax.make_mesh(shape, axes, axis_types=axis_types)
    arr = np.asarray(devices[:n]).reshape(shape)
    return Mesh(arr, axes, axis_types=axis_types)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def dp_axes(mesh: Mesh) -> Tuple[str, ...]:
    """Axes the batch shards over (everything except the TP axis)."""
    return tuple(a for a in mesh.axis_names if a != "model")


def slow_axis(mesh: Mesh) -> Optional[str]:
    return "pod" if "pod" in mesh.axis_names else None
