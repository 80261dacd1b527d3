"""SIMDRAM/MIMDRAM bit-serial arithmetic as a Pallas kernel (PuD-SSD model).

TPU adaptation (DESIGN.md §4a): Ambit's triple-row-activation MAJ/NOT over
vertically-laid-out bit-planes becomes vectorized bitwise logic on the VPU
over int tiles in VMEM.  The ripple-carry adder and shift-add multiplier
below use ONLY the PuD primitive set {AND, OR, XOR, NOT, shift} — the same
gate-level circuits SIMDRAM synthesizes — so the kernel is a functional
model of the in-DRAM computation, executed tile-by-tile in VMEM.

The TPU shifts only 32-bit lanes, so narrower tiles are sign-extended to
int32, run the ``bits``-wide circuit there and are truncated back: the low
``bits`` of the wide result are the wrapping narrow result.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _add_kernel(a_ref, b_ref, out_ref, *, bits: int):
    """Ripple-carry add via MAJ(=carry)/XOR(=sum) bit-plane circuit."""
    a = a_ref[...].astype(jnp.int32)
    b = b_ref[...].astype(jnp.int32)

    def body(_, carry):
        a, b = carry
        s = a ^ b                    # partial sum      (XOR row-op)
        c = (a & b) << 1             # carry, shifted   (MAJ row-op + shift)
        return s, c

    s, c = jax.lax.fori_loop(0, bits, body, (a, b))
    # carry fully propagated through the low ``bits`` after W steps
    out_ref[...] = (s | c).astype(out_ref.dtype)


def _mul_kernel(a_ref, b_ref, out_ref, *, bits: int):
    """Shift-add multiply: W partial products, each AND+add (bit-serial)."""
    a = a_ref[...].astype(jnp.int32)
    b = b_ref[...].astype(jnp.int32)
    acc = jnp.zeros_like(a)

    def body(i, acc):
        bit = (b >> i) & 1
        pp = jnp.where(bit == 1, a << i, 0)   # predicated partial product
        # bit-serial add of pp into acc (same MAJ/XOR circuit)
        def add_body(_, carry):
            x, y = carry
            return x ^ y, (x & y) << 1
        s, c = jax.lax.fori_loop(0, bits * 2, add_body, (acc, pp))
        return s | c

    out_ref[...] = jax.lax.fori_loop(0, bits, body, acc).astype(out_ref.dtype)


def _run(kernel, a, b, block_rows, block_cols, interpret):
    rows, cols = a.shape
    block_rows = min(block_rows, rows)
    block_cols = min(block_cols, cols)
    assert rows % block_rows == 0 and cols % block_cols == 0
    grid = (rows // block_rows, cols // block_cols)
    spec = pl.BlockSpec((block_rows, block_cols), lambda i, j: (i, j))
    return pl.pallas_call(
        kernel, grid=grid, in_specs=[spec, spec], out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype),
        interpret=interpret,
    )(a, b)


def bitserial_add(a: jnp.ndarray, b: jnp.ndarray, block_rows: int = 8,
                  block_cols: int = 512, interpret: bool = False):
    """Elementwise a+b via the bit-serial MAJ/XOR adder (int32/int8 tiles)."""
    bits = a.dtype.itemsize * 8
    return _run(functools.partial(_add_kernel, bits=bits), a, b,
                block_rows, block_cols, interpret)


def bitserial_mul(a: jnp.ndarray, b: jnp.ndarray, block_rows: int = 8,
                  block_cols: int = 512, interpret: bool = False):
    """Elementwise a*b via bit-serial shift-add partial products."""
    bits = a.dtype.itemsize * 8
    return _run(functools.partial(_mul_kernel, bits=bits), a, b,
                block_rows, block_cols, interpret)
