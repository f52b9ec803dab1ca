"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before any jax initialization).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """A mesh whose axes all use GSPMD's automatic sharding propagation
    (``jax.make_mesh`` now defaults to explicit axes, under which a
    gather on a sharded array must name its output sharding)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod ("data","model"); 2 pods stack a leading
    "pod" axis (the DCN dimension)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(*, data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests on CPU)."""
    n = len(jax.devices())
    assert data * model <= n, (data, model, n)
    return _auto_mesh((data, model), ("data", "model"))


def make_chip_mesh(n_chips: int):
    """1-D ("chips",) mesh for the sharded fused SpMM path — each chip
    owns a contiguous row range of the plan (core.spmm sharding)."""
    from ..core.spmm import chip_mesh
    return chip_mesh(n_chips)
