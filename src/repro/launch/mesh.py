"""Mesh construction (functions, not module-level constants, so
importing this module never touches jax device state).

The model code places nothing itself: shardings are given at the jit
boundary and GSPMD propagates them, which needs mesh axes of type
``Auto``. ``jax.make_mesh`` makes ``Explicit`` axes by default since
jax 0.7, under which plain ``jnp`` code such as the embedding gather is
refused, so every mesh of this repository comes from :func:`make_mesh`.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_cpu_mesh():
    """Single-device mesh with the production axis names (smoke tests)."""
    return make_mesh((1, 1), ("data", "model"))
