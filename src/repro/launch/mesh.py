"""Production mesh construction.

A function (not a module-level constant) so importing this module never touches
jax device state — the dry-run sets XLA_FLAGS before any jax import.
"""
from __future__ import annotations

from typing import Tuple

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 two-pod (512 chips) mesh."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """Mesh over ``jax.devices()`` whose axes are all ``Auto`` (GSPMD decides
    what it is not told)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
