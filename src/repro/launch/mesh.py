"""Production meshes.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* first jax
init, and smoke tests must keep seeing 1 device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """``jax.make_mesh`` with every axis ``Auto``-typed: the sharding rules
    (``distributed.sharding``) place arrays by ``NamedSharding`` and let
    the compiler propagate, which ``Explicit`` axes refuse for plain
    indexing gathers."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips, ("data", "model").
    Multi-pod: 2x16x16 = 512 chips, ("pod", "data", "model")."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model: int = 2, data: int = 2, pod: int = 1):
    """Small mesh over forced-host devices for distributed unit tests."""
    if pod > 1:
        return _auto_mesh((pod, data, model), ("pod", "data", "model"))
    return _auto_mesh((data, model), ("data", "model"))
