"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this
module never touches jax device state.  The dry-run entry point
(``launch/dryrun.py``) sets ``XLA_FLAGS=--xla_force_host_platform_device_count=512``
before any jax import; everything here just consumes whatever devices
exist.

Production target: TPU v5e pods — 256 chips/pod arranged (data=16,
model=16); multi-pod adds a leading ``pod`` axis (outer data parallelism
over DCN).  ICI links serve the intra-pod axes.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
from jax.sharding import AxisType, Mesh

__all__ = ["make_mesh_compat", "make_production_mesh", "make_test_mesh", "HW"]


def make_mesh_compat(shape: Tuple[int, ...], axes: Tuple[str, ...],
                     devices=None) -> Mesh:
    """``jax.make_mesh`` with every axis ``AxisType.Auto`` (GSPMD
    propagates shardings along them).  ``devices`` defaults to
    ``jax.devices()``; pass a subset to build a mesh on part of a host."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


class HW:
    """TPU v5e hardware constants used by the roofline analysis."""

    PEAK_FLOPS_BF16 = 197e12        # per chip
    HBM_BW = 819e9                  # bytes/s per chip
    ICI_BW = 50e9                   # bytes/s per link (~per axis direction)
    DCN_BW = 6.25e9                 # bytes/s per host NIC (50 Gbit)
    HBM_BYTES = 16 * 1024 ** 3      # 16 GiB per chip
    VMEM_BYTES = 128 * 1024 * 1024


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """(data=16, model=16) single pod; (pod=2, data=16, model=16) multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh_compat(shape, axes)


def make_test_mesh(shape: Tuple[int, ...] = (2, 4),
                   axes: Tuple[str, ...] = ("data", "model")) -> Mesh:
    """Small mesh for CPU tests (requires the forced device count)."""
    n = math.prod(shape)
    if len(jax.devices()) < n:
        raise RuntimeError(
            f"need {n} devices; set XLA_FLAGS=--xla_force_host_platform_"
            f"device_count={n} before importing jax")
    return make_mesh_compat(shape, axes)
