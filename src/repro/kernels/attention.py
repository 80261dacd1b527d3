"""Tiled attention with online softmax (FlashAttention-style Pallas kernel).

Grid = (heads, q-blocks, k-blocks).  Each step holds one q tile and one
k/v tile in VMEM; the running (max, normalizer, accumulator) online-softmax
state lives in VMEM scratch across the k axis, so neither the [seq, seq]
scores nor the whole K/V of a head are ever resident — VMEM use is fixed by
the block sizes, not the sequence length.  Causal attention skips the k
blocks above the diagonal and does not fetch them.

``flash_attention`` is forward only and on no model path: it serves the
kernel tests, ``benchmarks/kernel_bench.py`` and ``chip_smoke.py``.

``flash_mha`` is the model path's (``models/layers._sdpa`` on the TPU): the
flash attention kernels of ``jax.experimental.pallas.ops.tpu`` (forward, dk/dv
and dq) under a ``custom_vjp`` of its own. Its forward runs the library's
kernel with the output left in float32, from which the backward takes its row
sums ``di = do . o``. The library's own backward takes them from the output
rounded to q's dtype: then the backward's rows of dS miss summing to zero by
that rounding, and the miss lands on what the softmax cancels, the gradient
of a key bias (zero in exact arithmetic), about twice the noise the jnp
attention leaves there.

``decode_gqa_attention`` is the decode step's (``models/layers.
gqa_attention`` on the TPU): one query position per sequence against the
stacked K/V cache in its ``shbd`` order, read in place, and only its filled
prefix (``kv_blocks_read``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu import flash_attention as FA

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


def _mha_forward(q, k, v, causal: bool, block: int):
    """The library's forward kernel over q/k/v [B,H,S,dh] in square tiles of
    ``block``, writing its output in float32: (o, l, m), the softmax's row
    sums l and maxima m [B,H,S]. Causal grids re-point the k tiles above the
    diagonal at the next row's first, which is fetched instead."""
    b, h, s, dh = q.shape
    scale = 1.0 / np.sqrt(dh)
    lanes = FA.MIN_BLOCK_SIZE

    def q_map(bi, hi, qi, ki):
        return bi, hi, qi, 0

    def kv_map(bi, hi, qi, ki):
        if causal:
            ki = jax.lax.select(FA.below_or_on_diag(qi, block, ki, block),
                                ki, 0)
        return bi, hi, ki, 0

    tile = lambda width, index: pl.BlockSpec((1, 1, block, width), index)
    stat = jax.ShapeDtypeStruct((b, h, s, lanes), jnp.float32)
    out_shape = [jax.ShapeDtypeStruct(q.shape, jnp.float32), stat, stat]
    scratch = [] if block == s else [  # one k tile: no running statistics
        pltpu.VMEM((1, 1, block, lanes), jnp.float32),
        pltpu.VMEM((1, 1, block, lanes), jnp.float32),
        pltpu.VMEM((1, 1, block, dh), jnp.float32)]
    kernel = functools.partial(
        FA._flash_attention_kernel, causal=causal, sm_scale=scale,
        block_k=block, kv_seq_len=s, mask_value=FA.DEFAULT_MASK_VALUE)
    o, l, m = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0, grid=(b, h, s // block, s // block),
            in_specs=[tile(dh, q_map), tile(dh, kv_map), tile(dh, kv_map),
                      None, None, None],
            out_specs=[tile(dh, q_map), tile(lanes, q_map),
                       tile(lanes, q_map)],
            scratch_shapes=scratch),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "parallel", "arbitrary")),
        name=f"flash_mha_fwd_block_{block}",
        cost_estimate=FA._fwd_cost_estimate(
            q, k, v, None, None, causal=causal, sm_scale=scale,
            kernel_inputs_specs=(q, k, v), kernel_outputs_specs=out_shape),
    )(q, k, v, None, None, None)
    return o, l[..., 0], m[..., 0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_mha(q, k, v, causal: bool, block: int):
    """Attention over q/k/v [B,H,S,dh] (sq == sk, ``block`` dividing S) in
    q's dtype, scaled by 1/sqrt(dh), with a backward."""
    return _mha_forward(q, k, v, causal, block)[0].astype(q.dtype)


def _flash_mha_fwd(q, k, v, causal, block):
    o, l, m = _mha_forward(q, k, v, causal, block)
    return o.astype(q.dtype), (q, k, v, o, l, m)


def _flash_mha_bwd(causal, block, res, do):
    q, k, v, o, l, m = res
    di = jnp.sum(o * do.astype(jnp.float32), axis=-1)
    common = dict(sm_scale=1.0 / np.sqrt(q.shape[-1]), causal=causal,
                  mask_value=FA.DEFAULT_MASK_VALUE, debug=False)
    dk, dv = FA._flash_attention_bwd_dkv(
        q, k, v, None, None, l, m, do, di, block_q_major=block,
        block_q=block, block_k_major=block, block_k=block, **common)
    dq, _ = FA._flash_attention_bwd_dq(
        q, k, v, None, None, l, m, do, di, block_q_major=block,
        block_k_major=block, block_k=block, **common)
    return dq, dk, dv


flash_mha.defvjp(_flash_mha_fwd, _flash_mha_bwd)


# the decode kernel's positions a grid step: a v5e at the benchmark's
# shapes picks it (PERF.md section 6, PR 18)
DECODE_BLOCK = 256


def kv_blocks_read(index, block: int):
    """Blocks of ``block`` positions a decode step writing at ``index``
    reads: those holding positions 0..index, ceil((index + 1) / block)."""
    return index // block + 1


def _decode_kernel(meta_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                   acc_ref, *, block: int, scale: float):
    """One KV head (grid axis 0) and one group of G sequences (axis 1)
    over one block of positions (axis 2).

    q_ref [G, r*dh]: the head's r query heads side by side; k_ref and v_ref
    [block, G, dh]. The r*G query rows (head-major) meet the block's
    block*G key rows in one product; a row keeps the columns of its own
    sequence (``col % G == row % G``), so the softmax and the PV product see
    each sequence's keys alone. Blocks past the one holding ``index`` are
    neither fetched (the index map repeats that block) nor computed; in
    that block the positions past ``index`` are masked, and their values
    zeroed, so that no unfilled row (zeros, a former batch's, or NaN)
    reaches the output."""
    j = pl.program_id(2)
    index = meta_ref[1]
    last = kv_blocks_read(index, block) - 1
    g, width = q_ref.shape
    dh = k_ref.shape[-1]
    r = width // dh

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def _step(partial: bool):
        q = q_ref[...]
        q = jnp.concatenate([q[:, i * dh:(i + 1) * dh] for i in range(r)])
        k = k_ref[...].reshape(block * g, dh)       # row p * G + sequence
        v = v_ref[...].reshape(block * g, dh)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [r*G, block*G]
        # G is 8 or 16: bit masks and shifts stand for % G and // G
        iota = functools.partial(jax.lax.broadcasted_iota, jnp.int32, s.shape)
        keep = (iota(0) & (g - 1)) == (iota(1) & (g - 1))
        if partial:
            shift = g.bit_length() - 1
            keep &= j * block + (iota(1) >> shift) <= index
            pos = j * block + (jax.lax.broadcasted_iota(
                jnp.int32, (block * g, 1), 0) >> shift)
            v = jnp.where(pos <= index, v, jnp.zeros_like(v))
        s = jnp.where(keep, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    pl.when(j < last)(lambda: _step(False))
    pl.when(j == last)(lambda: _step(True))

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        out = acc_ref[...] / l_ref[...]              # [r*G, dh]
        o_ref[...] = jnp.concatenate(
            [out[i * g:(i + 1) * g] for i in range(r)], axis=1
        ).astype(o_ref.dtype)


def decode_gqa_attention(q, k, v, layer, index, block: int = DECODE_BLOCK,
                         interpret: bool = False):
    """Attention of one query position per sequence over layer ``layer``
    of the stacked K/V cache, positions 0..``index``.

    q [B, H, dh], B a multiple of 8; k, v [L, S, Hkv, B, dh]
    (``models/layers.kv_order``'s ``shbd``), read in place: each grid step
    fetches one KV head's ``block`` positions of 16 sequences (8 where 16
    do not divide B), and only the ``kv_blocks_read(index, block)`` blocks
    that hold the filled prefix. The H = Hkv * r query heads are grouped by
    KV head as ``gqa_attention`` groups them. Logits and the online softmax
    in float32, both products in the cache's dtype with float32
    accumulation, scaled by 1/sqrt(dh). Returns [B, H, dh]."""
    b, h, dh = q.shape
    _, s, hkv, _, _ = k.shape
    r = h // hkv
    g = 16 if b % 16 == 0 else 8
    block = min(block, s)
    meta = jnp.stack([jnp.asarray(layer, jnp.int32),
                      jnp.asarray(index, jnp.int32)])

    def kv_map(hi, gi, j, meta):
        return meta[0], jnp.minimum(
            j, kv_blocks_read(meta[1], block) - 1), hi, gi, 0

    kv_spec = pl.BlockSpec((None, block, None, g, dh), kv_map)
    q_spec = pl.BlockSpec((g, r * dh), lambda hi, gi, j, meta: (gi, hi))
    itemsize = k.dtype.itemsize
    out = pl.pallas_call(
        functools.partial(_decode_kernel, block=block,
                          scale=1.0 / np.sqrt(dh)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(hkv, b // g, pl.cdiv(s, block)),
            in_specs=[q_spec, kv_spec, kv_spec], out_specs=q_spec,
            scratch_shapes=[pltpu.VMEM((r * g, 1), jnp.float32),
                            pltpu.VMEM((r * g, 1), jnp.float32),
                            pltpu.VMEM((r * g, dh), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, h * dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="decode_gqa_attention",
        cost_estimate=pl.CostEstimate(   # at a full cache
            flops=4 * h * b * s * g * dh,
            transcendentals=h * b * s * g,
            bytes_accessed=2 * hkv * s * b * dh * itemsize
            + 2 * b * h * dh * q.dtype.itemsize),
        interpret=interpret,
    )(meta, q.reshape(b, h * dh), k, v)
    return out.reshape(b, h, dh)
