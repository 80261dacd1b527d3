"""INT8 quantized matmul with INT32 accumulation (MXU-tiled Pallas kernel).

The paper quantizes every workload to INT8 (§5.4); the LLM workloads'
dominant compute is INT8 GEMM.  The kernel tiles (M, N, K) with MXU-aligned
128-multiples blocks; the K grid axis accumulates into the output tile
(revisiting semantics), so one output block stays resident in VMEM across
all K steps — the standard TPU matmul schedule.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _matmul_kernel(a_ref, b_ref, out_ref):
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # int8 operands go to the MXU as they are; it accumulates in int32
    out_ref[...] += jax.lax.dot_general(
        a_ref[...], b_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)


def int8_matmul(a: jnp.ndarray, b: jnp.ndarray,
                block_m: int = 128, block_n: int = 128, block_k: int = 128,
                interpret: bool = False) -> jnp.ndarray:
    """``a[int8, M,K] @ b[int8, K,N] -> int32[M,N]``, MXU-aligned tiling."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2
    block_m = min(block_m, m)
    block_n = min(block_n, n)
    block_k = min(block_k, k)
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0, \
        f"shape ({m},{k})x({k},{n}) not tileable by ({block_m},{block_n},{block_k})"
    grid = (m // block_m, n // block_n, k // block_k)
    return pl.pallas_call(
        _matmul_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        interpret=interpret,
    )(a, b)
