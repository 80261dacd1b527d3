"""Flash-Cosmos multi-wordline-sensing bulk bitwise ops as a Pallas kernel.

TPU adaptation (DESIGN.md §4a): the flash page (one wordline's 16 KiB row)
maps to a VMEM-tiled (sublane x lane)-aligned block; "simultaneously
activating multiple wordlines" — a wired-AND across the stacked cells of a
NAND string — becomes an in-register reduce over the operand-stacked
leading axis *inside one VMEM tile*: every operand page is touched exactly
once and never round-trips to HBM between operands, the TPU-native analogue
of computing during a single array sense.

Layout: ``stack[n_ops, rows, cols]`` -> out ``[rows, cols]``.  The grid
tiles (rows, cols); each invocation reduces all n_ops in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_INIT = {"and": -1, "nand": -1, "or": 0, "nor": 0, "xor": 0}
_IS_AND = {"and", "nand"}
_NEGATE = {"nand", "nor"}


def _mws_kernel(stack_ref, out_ref, *, op: str, n_ops: int):
    acc = jnp.full(out_ref.shape, _INIT[op], dtype=out_ref.dtype)

    def body(i, acc):
        page = stack_ref[i]                       # one wordline's page
        if op in _IS_AND:
            return acc & page
        if op in ("or", "nor"):
            return acc | page
        return acc ^ page

    acc = jax.lax.fori_loop(0, n_ops, body, acc)
    if op in _NEGATE:
        acc = ~acc
    out_ref[...] = acc


def mws_bitwise(stack: jnp.ndarray, op: str = "and",
                block_rows: int = 8, block_cols: int = 512,
                interpret: bool = False) -> jnp.ndarray:
    """Bulk bitwise reduce over ``stack[n_ops, rows, cols]`` (int dtype).

    ``block_rows``/``block_cols`` define the VMEM tile; cols should be a
    multiple of 128 (TPU lane count) and rows a multiple of 8 (sublanes).
    """
    n_ops, rows, cols = stack.shape
    block_rows = min(block_rows, rows)
    block_cols = min(block_cols, cols)
    assert rows % block_rows == 0 and cols % block_cols == 0, \
        f"{rows}x{cols} not tileable by {block_rows}x{block_cols}"
    grid = (rows // block_rows, cols // block_cols)
    kernel = functools.partial(_mws_kernel, op=op, n_ops=n_ops)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec(
            (n_ops, block_rows, block_cols),
            lambda i, j: (0, i, j))],
        out_specs=pl.BlockSpec(
            (block_rows, block_cols), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((rows, cols), stack.dtype),
        interpret=interpret,
    )(stack)
