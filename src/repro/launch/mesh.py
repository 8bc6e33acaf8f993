"""Mesh builders.

Every mesh is a FUNCTION result (not a module-level constant) so importing
this module never touches jax device state — the dry-run sets XLA_FLAGS
before first jax init; smoke tests see the single real CPU device.

All meshes use ``AxisType.Auto`` axes: the pod engine places parameters
and batches with ``NamedSharding`` and lets GSPMD propagate the rest.
``jax.make_mesh`` defaults to explicit-sharding axes, under which an
embedding gather over a sharded table is a type error.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """An Auto-axis mesh of `shape` over `axes` (on `devices` when given,
    else the default devices)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod (TPU v5e pod slice); the multi-pod mesh
    adds a leading "pod" axis of 2 (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Single-device mesh for CPU smoke tests (1×1, same axis names)."""
    return make_mesh((1, 1), ("data", "model"))
