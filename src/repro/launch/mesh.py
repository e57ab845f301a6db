"""Mesh construction (TPU v5e pods and local device sets).

Functions, not module-level constants, so importing this module never
touches jax device state (the dry-run must set XLA_FLAGS *before* any jax
initialization).

Every program mesh goes through :func:`make_mesh`: ``jax.make_mesh``
defaults to Explicit axis types, while this code base shards with
``with_sharding_constraint`` and ``shard_map`` under Auto axes (an
Explicit axis rejects both the constraint and gathers on sharded arrays).
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType

# TPU v5e hardware constants used by the roofline analysis
PEAK_FLOPS_BF16 = 197e12  # per chip, FLOP/s
HBM_BW = 819e9  # bytes/s per chip
ICI_BW = 50e9  # bytes/s per link


def make_mesh(shape: Sequence[int], axes: Sequence[str], *, devices=None):
    """``jax.make_mesh`` with every axis Auto."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def dp_axes(mesh) -> tuple:
    """The data-parallel axis group: ('pod','data') on multi-pod meshes."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))
