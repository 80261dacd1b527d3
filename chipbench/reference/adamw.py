"""Plain AdamW step of a training cell's check, in float32, independent of
the program (it imports nothing of ``repro``).

The learning rate at step ``step`` (the count of steps taken before this
one): a linear warm-up, ``base_lr * (step + 1) / warmup`` while ``step <
warmup``, then a cosine from ``base_lr`` down to ``final_frac * base_lr``
at ``total``.

The step: the gradients scaled by ``min(1, clip_norm / (global norm +
1e-9))``; ``m = b1 m + (1 - b1) g`` and ``v = b2 v + (1 - b2) g^2``, each
bias-corrected by ``1 - b ** (step + 1)``; ``p - lr (m_hat / (sqrt(v_hat) +
eps) + weight_decay p)`` with every leaf decayed. The new params are stored
in ``dtype``, the configuration's, rounded to nearest.
"""
from __future__ import annotations

import functools
import math
from typing import Mapping

import jax
import jax.numpy as jnp

F32 = jnp.float32


def lr_at(step: int, base_lr: float, total: int, warmup: int,
          final_frac: float) -> float:
    if step < warmup:
        return base_lr * (step + 1) / warmup
    t = min(max((step - warmup) / max(1, total - warmup), 0.0), 1.0)
    return base_lr * (final_frac
                      + (1 - final_frac) * 0.5 * (1 + math.cos(math.pi * t)))


@functools.partial(jax.jit, static_argnames=("dtype",))
def _leaf(p, g, m, v, scale, lr, bc1, bc2, b1, b2, eps, weight_decay,
          dtype):
    g = g.astype(F32) * scale
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    upd = (m / bc1) / (jnp.sqrt(v / bc2) + eps) + weight_decay * p
    return (p - lr * upd).astype(dtype)


def new_params(params: Mapping, grads: Mapping, mu: Mapping, nu: Mapping,
               step: int, lr: float, dtype, b1: float, b2: float, eps: float,
               weight_decay: float, clip_norm: float) -> dict:
    """{name: new param} of one step; ``params`` (float32), ``grads``,
    ``mu`` and ``nu`` are dicts of leaves by name. The moments may be host
    arrays: each is placed as its gradient is."""
    norm = math.sqrt(sum(float(jnp.sum(jnp.square(g.astype(F32))))
                         for g in grads.values()))
    scale = min(1.0, clip_norm / (norm + 1e-9))
    t = step + 1
    return {k: _leaf(p, grads[k], jax.device_put(mu[k], grads[k].sharding),
                     jax.device_put(nu[k], grads[k].sharding), scale, lr,
                     1 - b1 ** t, 1 - b2 ** t, b1, b2, eps, weight_decay,
                     dtype=jnp.dtype(dtype))
            for k, p in params.items()}
