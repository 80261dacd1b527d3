"""Mesh construction.

A function (not a module-level constant) so importing never touches jax
device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """Mesh whose axes GSPMD shards automatically: the model code gives
    sharding hints, not explicit per-op shardings."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))
