"""Production mesh construction.

A function (not a module-level constant) so importing never touches jax
device state.  Single pod: 16x16 = 256 chips, (data, model).  Multi-pod:
2 pods x 256 = 512 chips, (pod, data, model) — the "pod" axis carries
data-parallel gradient reduction over the inter-pod links.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes, devices=None):
    """Mesh whose axes GSPMD shards automatically: the model code gives
    sharding hints, not explicit per-op shardings."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def mesh_axes(mesh) -> tuple:
    """(data_axes, model_axis) for a mesh built by make_production_mesh."""
    names = mesh.axis_names
    model = "model" if "model" in names else None
    data = tuple(a for a in names if a in ("pod", "data"))
    return data, model


def chips(mesh) -> int:
    return mesh.devices.size
