"""Mesh construction.

Defined as a function (never a module-level constant) so importing this
module never touches jax device state; it consumes whatever devices
exist.
"""

from __future__ import annotations

from typing import Tuple

import jax
from jax.sharding import AxisType, Mesh

__all__ = ["make_mesh_compat"]


def make_mesh_compat(shape: Tuple[int, ...], axes: Tuple[str, ...],
                     devices=None) -> Mesh:
    """``jax.make_mesh`` with every axis ``AxisType.Auto`` (GSPMD
    propagates shardings along them).  ``devices`` defaults to
    ``jax.devices()``; pass a subset to build a mesh on part of a host."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)
