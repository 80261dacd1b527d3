"""Ares-Flash latch-based shift-and-add multiply as a Pallas kernel (IFP).

Ares-Flash extends the flash plane's page-buffer latches (S/A/B/C) with
transmission gates so a page can be ANDed with a broadcast bit, shifted,
and accumulated — integer multiply as W latch-level shift-add rounds.

TPU adaptation: each "latch round" is one VPU pass over the VMEM tile; the
broadcast multiplier bit is extracted per element (the in-flash version
broadcasts one operand bit-plane per round).  Only the low ``bits`` of the
multiplier participate, exactly like the latch datapath width.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _shift_add_kernel(a_ref, b_ref, out_ref, *, bits: int):
    # 32-bit lanes only shift on the TPU; the truncating cast back wraps
    a = a_ref[...].astype(jnp.int32)
    b = b_ref[...].astype(jnp.int32)
    acc = jnp.zeros_like(a)

    def round_(i, acc):
        bit = (b >> i) & 1                      # latch-broadcast bit plane
        return acc + jnp.where(bit == 1, a << i, 0)

    out_ref[...] = jax.lax.fori_loop(0, bits, round_, acc).astype(
        out_ref.dtype)


def shift_add_mul(a: jnp.ndarray, b: jnp.ndarray, bits: int = 8,
                  block_rows: int = 8, block_cols: int = 512,
                  interpret: bool = False) -> jnp.ndarray:
    """a * (b & ((1<<bits)-1)) via the Ares-Flash shift-and-add datapath."""
    rows, cols = a.shape
    block_rows = min(block_rows, rows)
    block_cols = min(block_cols, cols)
    assert rows % block_rows == 0 and cols % block_cols == 0
    grid = (rows // block_rows, cols // block_cols)
    spec = pl.BlockSpec((block_rows, block_cols), lambda i, j: (i, j))
    return pl.pallas_call(
        functools.partial(_shift_add_kernel, bits=bits),
        grid=grid, in_specs=[spec, spec], out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype),
        interpret=interpret,
    )(a, b)
