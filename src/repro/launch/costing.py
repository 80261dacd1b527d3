"""Trip-count-corrected cost extraction from compiled dry-run artifacts.

XLA's HloCostAnalysis counts a while-loop body ONCE regardless of trip
count (verified empirically), so a scan-over-layers model under-reports
FLOPs/bytes/collective traffic by ~the layer count.  We correct by
compiling a per-segment *probe* — one layer body with the identical
sharded shapes (forward for serving cells; forward+backward(+remat
recompute) for training cells) — and adding ``(count-1) x probe_cost`` to
the aggregate numbers.

All reported numbers are PER-DEVICE (the compiled module is the per-device
program), matching the per-chip roofline terms.
"""
from __future__ import annotations

import functools
import re
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.launch import sharding as SH
from repro.launch.mesh import mesh_axes
from repro.models import layers as L
from repro.models import model as M
from repro.models.config import ArchConfig

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
                "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8, "c64": 8, "c128": 16}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_OP_RE = re.compile(r"([a-z][a-z0-9\-]*)\(")


def _result_bytes(line: str) -> int:
    """Bytes of an HLO op's result — the type(s) between '=' and the op."""
    parts = line.split(" = ", 1)
    if len(parts) != 2:
        return 0
    rhs = parts[1]
    m = _OP_RE.search(rhs)
    head = rhs[:m.start()] if m else rhs
    total = 0
    for sm in _SHAPE_RE.finditer(head):
        dt, dims = sm.groups()
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-device result bytes of every collective op in post-SPMD HLO."""
    out = {k: 0 for k in _COLLECTIVES}
    ops = 0
    for line in hlo_text.splitlines():
        s = line.strip()
        m = _OP_RE.search(s.split(" = ", 1)[1]) if " = " in s else None
        if not m:
            continue
        op = m.group(1)
        for kind in _COLLECTIVES:
            if op == kind or op.startswith(kind + "-"):
                out[kind] += _result_bytes(s)
                ops += 1
                break
    out["ops"] = ops
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    return out


def costs_of(compiled) -> Dict[str, float]:
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    text = compiled.as_text()
    coll = collective_bytes(text)
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "collectives": coll}


def _one_layer_params_sds(cfg: ArchConfig, kind: str, mesh):
    data, model = mesh_axes(mesh)
    shapes = jax.eval_shape(
        lambda k: M._block_init(kind, k, cfg, jnp.dtype(cfg.dtype)),
        jax.random.PRNGKey(0))
    specs = jax.tree_util.tree_map_with_path(
        lambda path, leaf: SH.param_spec_for(path, leaf.shape, mesh, data,
                                             model),
        shapes)
    return SH.to_sds(shapes, specs, mesh)


def _x_sds(cfg: ArchConfig, batch: int, seq: int, mesh):
    data, model = mesh_axes(mesh)
    shape = (batch, seq, cfg.d_model)
    spec = SH._fit(mesh, shape, [data or None, None, model])
    return jax.ShapeDtypeStruct(shape, jnp.dtype(cfg.dtype),
                                sharding=NamedSharding(mesh, spec))


def _cache_sds(cfg: ArchConfig, kind: str, batch: int, seq: int, mesh):
    """One layer's cache as ``block_apply`` takes it: GQA ``k``/``v`` as a
    stack of one layer (``M._with_index``), other caches and states
    without the layer dim."""
    layer = jax.eval_shape(
        functools.partial(M._block_cache, kind, cfg, batch, seq))
    data, model = mesh_axes(mesh)
    stacked = "k" in layer

    def sds(path, leaf):
        # cache_spec_for expects a leading layer dim
        shape = (1,) + leaf.shape
        spec = tuple(SH.cache_spec_for(path, shape, mesh, data, model,
                                       L.kv_order(cfg, batch)))
        if not stacked:
            shape, spec = shape[1:], spec[1:]
        return jax.ShapeDtypeStruct(shape, leaf.dtype,
                                    sharding=NamedSharding(mesh, P(*spec)))
    return jax.tree_util.tree_map_with_path(sds, layer)


def probe_segment(cfg: ArchConfig, kind: str, step_kind: str,
                  batch: int, seq: int, mesh) -> Dict[str, float]:
    """Compile one layer body with cell-identical sharded shapes and return
    its per-device cost record (plus 'fwd' sub-record for train remat)."""
    body_kind = "attn" if kind == "sattn" else kind
    p_sds = _one_layer_params_sds(cfg, body_kind, mesh)
    positions = jax.ShapeDtypeStruct((batch, seq), jnp.int32,
                                     sharding=NamedSharding(
                                         mesh, SH.batch_spec((batch, seq),
                                                             mesh)))

    if step_kind == "train":
        x_sds = _x_sds(cfg, batch, seq, mesh)

        def fwd(p_l, x, pos):
            out, _ = M.block_apply(body_kind, cfg, p_l, x, pos)
            return out

        def fwdbwd(p_l, x, pos):
            def g(p_l, x):
                return fwd(p_l, x, pos).astype(jnp.float32).sum()
            return jax.grad(g, argnums=(0, 1))(p_l, x)

        with jax.set_mesh(mesh):
            c_fwd = jax.jit(fwd).lower(p_sds, x_sds, positions).compile()
            c_fb = jax.jit(fwdbwd).lower(p_sds, x_sds, positions).compile()
        fwd_cost = costs_of(c_fwd)
        fb = costs_of(c_fb)
        if cfg.remat:
            # scan+checkpoint executes fwd once and (fwd + bwd) at grad time
            for k in ("flops", "bytes"):
                fb[k] += fwd_cost[k]
            for k in fb["collectives"]:
                fb["collectives"][k] += fwd_cost["collectives"][k]
        return fb

    # serving: decode (seq=1 against cache) or prefill (cache fill)
    cache_sds = _cache_sds(cfg, body_kind, batch, seq, mesh)
    qlen = 1 if step_kind == "decode" else seq
    x_sds = _x_sds(cfg, batch, qlen, mesh)
    pos_q = jax.ShapeDtypeStruct((batch, qlen), jnp.int32,
                                 sharding=NamedSharding(
                                     mesh, SH.batch_spec((batch, qlen),
                                                         mesh)))

    def serve_body(p_l, x, pos, cache):
        cache_in = M._with_index(cache, jnp.int32(0))
        out, nc = M.block_apply(body_kind, cfg, p_l, x, pos, cache_in)
        return out, M._strip_index(nc)

    with jax.set_mesh(mesh):
        c = jax.jit(serve_body).lower(p_sds, x_sds, pos_q,
                                      cache_sds).compile()
    return costs_of(c)


def corrected_costs(cfg: ArchConfig, step_kind: str, batch: int, seq: int,
                    mesh, agg: Dict[str, float]) -> Dict[str, float]:
    """agg (whole-cell compile, bodies counted once) + (count-1) x probes."""
    out = {"flops": agg["flops"], "bytes": agg["bytes"],
           "collectives": dict(agg["collectives"])}
    probes = {}
    for kind, count in M.segments_of(cfg):
        reps = count - 1
        if kind == "sattn":
            # shared attn blocks are unrolled in the HLO already
            continue
        if reps <= 0:
            continue
        if kind not in probes:
            probes[kind] = probe_segment(cfg, kind, step_kind, batch, seq,
                                         mesh)
        pr = probes[kind]
        out["flops"] += reps * pr["flops"]
        out["bytes"] += reps * pr["bytes"]
        for k in pr["collectives"]:
            out["collectives"][k] = (out["collectives"].get(k, 0)
                                     + reps * pr["collectives"][k])
    if cfg.enc_layers > 1 and step_kind in ("train", "prefill"):
        # encoder scan: approximate with the decoder block probe family
        pass
    return out
