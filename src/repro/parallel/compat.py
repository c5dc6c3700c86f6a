"""Manual-SPMD entry points: ``shard_map``, ``use_mesh`` and ``make_mesh``.

Every mesh and manual-SPMD call site in the repo goes through this module
(the ``mesh-entry`` lint rule enforces it), so the sharding API the
program depends on lives in one place.

Meshes are always built with ``AxisType.Auto`` axes. ``jax.make_mesh``
defaults to ``Explicit`` axes, under which ``with_sharding_constraint``
raises and scan carries change type; the model code relies on the
compiler propagating shardings, which is what ``Auto`` means.
"""
from __future__ import annotations

import math
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = True,
              **kwargs):
    """``jax.shard_map`` with the mesh passed explicitly."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma, **kwargs)


def use_mesh(mesh: Mesh):
    """Context manager activating ``mesh`` for the enclosed computation."""
    return jax.set_mesh(mesh)


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices: Optional[Sequence] = None) -> Mesh:
    """Build a mesh of ``shape`` with named ``axes``, all ``Auto``.
    ``devices`` (default ``jax.devices()``) fills it in row-major order."""
    shape, axes = tuple(shape), tuple(axes)
    axis_types = (AxisType.Auto,) * len(axes)
    if devices is None:
        return jax.make_mesh(shape, axes, axis_types=axis_types)
    devs = np.asarray(devices)[:math.prod(shape)].reshape(shape)
    return Mesh(devs, axes, axis_types=axis_types)
