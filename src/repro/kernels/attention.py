"""Tiled attention with online softmax (FlashAttention-style Pallas kernel).

Grid = (heads, q-blocks, k-blocks).  Each step holds one q tile and one
k/v tile in VMEM; the running (max, normalizer, accumulator) online-softmax
state lives in VMEM scratch across the k axis, so neither the [seq, seq]
scores nor the whole K/V of a head are ever resident — VMEM use is fixed by
the block sizes, not the sequence length.  Causal attention skips the k
blocks above the diagonal and does not fetch them.

Not on the model path: ``models/layers.py`` runs its own chunked jnp
attention on every backend.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, out_ref, m_ref, l_ref, acc_ref, *,
                 block_q: int, block_k: int, causal: bool, scale: float):
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def _step():
        v = v_ref[0]
        # operands enter the MXU in their own dtype, fp32 accumulation
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # [bq, bk]
        if causal:
            iota = functools.partial(jax.lax.broadcasted_iota, jnp.int32,
                                     (block_q, block_k))
            q_pos = qi * block_q + iota(0)
            k_pos = ki * block_k + iota(1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    if causal:   # k blocks wholly above the diagonal contribute nothing
        pl.when(ki * block_k < (qi + 1) * block_q)(_step)
    else:
        _step()

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finish():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        out_ref[0] = out.astype(out_ref.dtype)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    causal: bool = True, scale: float | None = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False) -> jnp.ndarray:
    """Attention over ``q/k/v [heads, seq, dh]`` with online softmax.

    Causal masking aligns query 0 with key 0 (prefill of ``sq == sk``)."""
    h, sq, d = q.shape
    _, sk, _ = k.shape
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    assert sq % block_q == 0 and sk % block_k == 0
    if scale is None:
        scale = 1.0 / np.sqrt(d)

    def kv_index(hh, qq, kk):
        if causal:   # re-point skipped blocks at the last one used: no fetch
            kk = jnp.minimum(kk, ((qq + 1) * block_q - 1) // block_k)
        return hh, kk, 0

    kernel = functools.partial(
        _attn_kernel, block_q=block_q, block_k=block_k, causal=causal,
        scale=scale)
    return pl.pallas_call(
        kernel,
        grid=(h, sq // block_q, sk // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda hh, qq, kk: (hh, qq, 0)),
            pl.BlockSpec((1, block_k, d), kv_index),
            pl.BlockSpec((1, block_k, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, block_q, d),
                               lambda hh, qq, kk: (hh, qq, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, 1), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32),
                        pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
