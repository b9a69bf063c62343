"""Production mesh builders.

A FUNCTION, not a module-level constant — importing this module never
touches jax device state (jax locks the device count on first init, and the
dry-run needs to set XLA_FLAGS first).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: tuple, axes: tuple):
    """``jax.make_mesh`` with ``Auto`` axes.  Its default (``Explicit``)
    types every array by its sharding, so slicing or gathering a
    ``shard_map`` result outside the map raises ``ShardingTypeError``;
    the engine's round loops and their callers rely on ``Auto``."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 chips per pod (v5e pod slice); multi-pod adds a leading 'pod'
    axis (2 pods = 512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1, pod: int | None = None):
    """Small mesh over host devices (tests / CPU-distributed runs)."""
    if pod is not None:
        return _auto_mesh((pod, data, model), ("pod", "data", "model"))
    return _auto_mesh((data, model), ("data", "model"))
