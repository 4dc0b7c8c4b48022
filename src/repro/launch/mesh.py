"""Production mesh builders (functions — importing never touches devices).

All repo code and tests build meshes through ``make_mesh``.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], *,
              devices: Optional[Sequence] = None):
    """``jax.make_mesh`` with every axis ``Auto``: the models place arrays
    with sharding constraints and leave propagation to the compiler."""
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=(AxisType.Auto,) * len(axis_names),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips (v5e pod).  Multi-pod: 2 pods = 512."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(data: int = 2, model: int = 4):
    """Small mesh over however many (possibly forced-host) devices exist."""
    n = len(jax.devices())
    if data * model > n:
        data, model = 1, n
    return make_mesh((data, model), ("data", "model"))
