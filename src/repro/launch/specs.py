"""input_specs: ShapeDtypeStruct stand-ins for every (arch x shape) cell.

Weak-type-correct, sharded, zero-allocation argument trees (params,
optimizer state, caches, batches) for the dry-run's ``.lower()`` — the
pattern that proves the distribution config is coherent without hardware.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.configs.shapes import SHAPES, ShapeSpec, applicable
from repro.launch import sharding as SH
from repro.launch.steps import (build_prefill_step, build_serve_step,
                                build_train_step, extra_inputs)
from repro.models import model as M
from repro.models.config import ArchConfig
from repro.optim.adamw import adamw_init


def params_shapes(cfg: ArchConfig):
    return jax.eval_shape(lambda k: M.init_params(cfg, k),
                          jax.random.PRNGKey(0))


def opt_shapes(cfg: ArchConfig, p_shapes):
    return jax.eval_shape(adamw_init, p_shapes)


def cache_shapes(cfg: ArchConfig, batch: int, max_seq: int):
    return jax.eval_shape(
        functools.partial(M.init_cache, cfg, batch, max_seq))


def _sharded(tree_shapes, spec_tree, mesh):
    return SH.to_sds(tree_shapes, spec_tree, mesh)


def input_specs(arch: str, shape: str, mesh) -> Tuple[Callable, Tuple, str]:
    """Returns (step_fn, example_args_SDS, kind) for one cell.

    kind in {train, prefill, decode}.  Raises ValueError for inapplicable
    cells (long_500k on pure full-attention archs) with the skip reason.
    """
    cfg = configs.get(arch)
    spec: ShapeSpec = SHAPES[shape]
    ok, reason = applicable(cfg, shape)
    if not ok:
        raise ValueError(reason)
    p_shapes = params_shapes(cfg)
    p_specs = SH.param_specs(cfg, p_shapes, mesh)
    params_sds = _sharded(p_shapes, p_specs, mesh)

    b, s = spec.global_batch, spec.seq_len

    def tok_sds(shape_, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(
            shape_, dtype,
            sharding=NamedSharding(mesh, SH.batch_spec(shape_, mesh)))

    extras = extra_inputs(cfg, b, min(s, 4096) if spec.kind == "train" else s)

    def extras_sds():
        out = {}
        for k, v in extras.items():
            if k == "extra_embeds" or k == "enc_feats":
                out[k] = jax.ShapeDtypeStruct(
                    v.shape, v.dtype, sharding=NamedSharding(
                        mesh, SH.embeds_spec(v.shape, mesh)))
            else:
                out[k] = jax.ShapeDtypeStruct(
                    v.shape, v.dtype, sharding=NamedSharding(mesh, P()))
        return out

    if spec.kind == "train":
        o_shapes = opt_shapes(cfg, p_shapes)
        opt_sds = _sharded(o_shapes, SH.opt_specs(o_shapes, mesh), mesh)
        batch = {"tokens": tok_sds((b, s)), "labels": tok_sds((b, s))}
        batch.update(extras_sds())
        fn = build_train_step(cfg)
        return fn, (params_sds, opt_sds, batch), "train"

    if spec.kind == "prefill":
        n_extra = (extras["extra_embeds"].shape[1]
                   if "extra_embeds" in extras else 0)
        c_shapes = cache_shapes(cfg, b, s + n_extra)
        c_specs = SH.cache_specs(cfg, c_shapes, mesh)
        caches_sds = _sharded(c_shapes, c_specs, mesh)
        batch = {"tokens": tok_sds((b, s))}
        batch.update(extras_sds())
        fn = build_prefill_step(cfg)
        return fn, (params_sds, caches_sds, batch), "prefill"

    # decode: one new token against a seq_len-deep cache
    c_shapes = cache_shapes(cfg, b, s)
    c_specs = SH.cache_specs(cfg, c_shapes, mesh)
    caches_sds = _sharded(c_shapes, c_specs, mesh)
    token = tok_sds((b,))
    index = jax.ShapeDtypeStruct((), jnp.int32,
                                 sharding=NamedSharding(mesh, P()))
    fn = build_serve_step(cfg)
    args = [params_sds, caches_sds, token, index]
    if cfg.enc_layers:
        enc_shape = (b, max(8, min(s, 4096) // 4), cfg.d_model)
        args.append(jax.ShapeDtypeStruct(
            enc_shape, jnp.bfloat16,
            sharding=NamedSharding(mesh, SH.embeds_spec(enc_shape, mesh))))
    return fn, tuple(args), "decode"
