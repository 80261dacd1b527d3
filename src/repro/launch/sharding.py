"""Sharding plans: parameter / optimizer / cache / batch PartitionSpecs.

The plan is name-rule-driven 2D sharding: tensor-parallel over
"model" (attention heads, FFN columns, expert dim, vocab-free embedding
feature dim) and FSDP-style weight sharding over "data" (+"pod").  Every
axis assignment is divisibility-checked against the mesh and dropped when
it does not divide (e.g. 2-head KV caches on a 16-way model axis shard the
sequence dimension instead), so every architecture lowers on any mesh.
These plans are fixed: the name rules decide them, and nothing searches
over them. The model's own sharding hints (``models/layers.py``) follow
the active mesh with the same axis naming rule, ``mesh_axes``.
"""
from __future__ import annotations

import functools
from typing import Any, Optional, Tuple

import jax
from jax.sharding import PartitionSpec as P

from repro.models.config import ArchConfig
from repro.models.layers import kv_order, mesh_axes

# column-parallel leaves (shard last dim over "model", -2 over data/FSDP)
_COL = {"wq", "wk", "wv", "w1", "w3", "w_uq", "w_uk", "w_uv", "w_q",
        "w_in", "w_bc", "w_dt", "w_gates", "w_if", "r_gates", "w_dkv",
        "w_dq", "router"}
# row-parallel leaves (shard -2 over "model", last over data/FSDP)
_ROW = {"wo", "w2", "w_out"}


def _axis_size(mesh, entry) -> int:
    if entry is None:
        return 1
    if isinstance(entry, tuple):
        return int(functools.reduce(
            lambda a, b: a * b, (mesh.shape[e] for e in entry), 1))
    return int(mesh.shape[entry])


def _fit(mesh, shape, spec_entries) -> P:
    """Drop axis assignments whose mesh extent does not divide the dim."""
    out = []
    for dim, entry in zip(shape, spec_entries):
        if entry is None:
            out.append(None)
            continue
        size = _axis_size(mesh, entry)
        out.append(entry if dim % size == 0 else None)
    return P(*out)


def _leaf_name(path) -> str:
    for entry in reversed(path):
        if hasattr(entry, "key"):
            return str(entry.key)
    return ""


def _in_subtree(path, name: str) -> bool:
    return any(getattr(e, "key", None) == name for e in path)


def param_spec_for(path, shape, mesh, data: Tuple[str, ...],
                   model: Optional[str]) -> P:
    name = _leaf_name(path)
    nd = len(shape)
    dataspec = data if data else None
    if name in ("emb", "unemb"):
        if name == "emb":   # [V, D] -> feature dim over (data, model)
            combined = tuple(a for a in (data + ((model,) if model else ()))
                             if a)
            return _fit(mesh, shape, [None, combined or None])
        return _fit(mesh, shape, [tuple(data + ((model,) if model else ())) or
                                  None, None])
    if _in_subtree(path, "experts") and nd >= 3:
        # [L, E, D, F] / [L, E, F, D]: expert-parallel over model, FSDP over
        # the contraction dim.
        spec = [None] * nd
        spec[nd - 3] = model
        spec[nd - 2] = dataspec
        return _fit(mesh, shape, spec)
    if name in _COL and nd >= 2:
        spec = [None] * nd
        spec[nd - 1] = model
        spec[nd - 2] = dataspec
        return _fit(mesh, shape, spec)
    if name in _ROW and nd >= 2:
        spec = [None] * nd
        spec[nd - 1] = dataspec
        spec[nd - 2] = model
        return _fit(mesh, shape, spec)
    if name in ("conv_w", "a_log", "d_skip") and nd >= 1:
        spec = [None] * nd
        spec[nd - 1] = model
        return _fit(mesh, shape, spec)
    return P()   # norms and other small leaves: replicated


def param_specs(cfg: ArchConfig, params_shapes: Any, mesh) -> Any:
    data, model = mesh_axes(mesh)
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: param_spec_for(path, leaf.shape, mesh, data,
                                          model),
        params_shapes)


def opt_specs(opt_shapes: Any, mesh) -> Any:
    """AdamW moments follow their parameter's rule; the step is
    replicated."""
    data, model = mesh_axes(mesh)
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: param_spec_for(path, leaf.shape, mesh, data,
                                          model)
        if leaf.ndim > 0 else P(),
        opt_shapes)


def cache_spec_for(path, shape, mesh, data, model, order: str) -> P:
    """``order``: one K/V layer's axis order, ``kv_order``."""
    name = _leaf_name(path)
    nd = len(shape)
    dataspec = data if data else None
    spec = [None] * nd
    if name in ("k", "v"):            # [L] + one layer in ``order``
        spec[1 + order.index("b")] = dataspec
        spec[1 + order.index("s")] = model    # sequence-sharded cache
    elif name in ("latent", "k_rope"):  # [L, B, S, r]
        spec[1] = dataspec
        spec[2] = model
    elif name == "h" and nd == 4:     # mamba state [L, B, di, N]
        spec[1] = dataspec
        spec[2] = model
    elif name == "conv":              # [L, B, K-1, di]
        spec[1] = dataspec
        spec[3] = model
    elif name in ("c", "n", "m", "hid"):
        spec[1] = dataspec
    elif nd >= 2:
        spec[1] = dataspec
    return _fit(mesh, shape, spec)


def cache_specs(cfg: ArchConfig, cache_shapes: Any, mesh,
                batch: int) -> Any:
    data, model = mesh_axes(mesh)
    order = kv_order(cfg, batch)
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: cache_spec_for(path, leaf.shape, mesh, data,
                                          model, order),
        cache_shapes)


def batch_spec(shape, mesh) -> P:
    """Token batches: batch dim over (pod, data)."""
    data, model = mesh_axes(mesh)
    spec = [data if data else None] + [None] * (len(shape) - 1)
    return _fit(mesh, shape, spec)

