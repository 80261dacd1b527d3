"""AdamW with fp32 moments over (possibly bf16) params, global-norm clip.

State layout keeps moments in the same pytree structure as params so
jit/pjit shards them identically to the weights (ZeRO-style when the caller
adds a sharding rule over the data axis).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp


class AdamWState(NamedTuple):
    step: jnp.ndarray
    mu: Any
    nu: Any


def adamw_init(params) -> AdamWState:
    zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
    return AdamWState(
        step=jnp.zeros((), jnp.int32),
        mu=jax.tree_util.tree_map(zeros, params),
        nu=jax.tree_util.tree_map(zeros, params),
    )


def global_norm(tree) -> jnp.ndarray:
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                        for l in leaves))


def adamw_update(params, grads, state: AdamWState, lr,
                 b1: float = 0.9, b2: float = 0.95, eps: float = 1e-8,
                 weight_decay: float = 0.1, clip_norm: float = 1.0):
    """One AdamW step; returns (new_params, new_state, metrics). Its ops,
    the clip's global norm included, are named ``optimizer``."""
    with jax.named_scope("optimizer"):
        gnorm = global_norm(grads)
        scale = jnp.minimum(1.0, clip_norm / (gnorm + 1e-9))
        step = state.step + 1
        t = step.astype(jnp.float32)
        bc1 = 1 - b1 ** t
        bc2 = 1 - b2 ** t

        def upd(p, g, m, v):
            g = g.astype(jnp.float32) * scale
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * g * g
            mhat = m / bc1
            vhat = v / bc2
            delta = (mhat / (jnp.sqrt(vhat) + eps)
                     + weight_decay * p.astype(jnp.float32))
            return (p.astype(jnp.float32) - lr * delta).astype(p.dtype), m, v

        flat_p, tdef = jax.tree_util.tree_flatten(params)
        flat_g = jax.tree_util.tree_leaves(grads)
        flat_m = jax.tree_util.tree_leaves(state.mu)
        flat_v = jax.tree_util.tree_leaves(state.nu)
        out = [upd(p, g, m, v) for p, g, m, v in
               zip(flat_p, flat_g, flat_m, flat_v)]
        new_p = jax.tree_util.tree_unflatten(tdef, [o[0] for o in out])
        new_m = jax.tree_util.tree_unflatten(tdef, [o[1] for o in out])
        new_v = jax.tree_util.tree_unflatten(tdef, [o[2] for o in out])
        return new_p, AdamWState(step, new_m, new_v), {"grad_norm": gnorm}
