"""Abstract shapes (``jax.eval_shape``, no allocation) of the params, the
AdamW state and the decode caches of an architecture."""
from __future__ import annotations

import functools

import jax

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
