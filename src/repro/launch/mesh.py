"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — smoke tests and benches must keep seeing 1 CPU
device; only the dry-run sets ``xla_force_host_platform_device_count=512``.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax


def _make(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    # Auto axes: shardings propagate through GSPMD (our semantics)
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """v5e-256 single pod (data=16, model=16) or 2 pods = 512 chips
    (pod=2, data=16, model=16)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """Arbitrary mesh (tests / elastic remesh)."""
    return _make(shape, axes)
