"""Jit'd public wrappers for the Pallas kernels (shape checks + padding).

Every kernel runs compiled unless the caller passes ``interpret=True``,
which only a non-TPU backend accepts: CPU tests ask for it explicitly, and
arrays that live on a TPU are refused it.  Nothing is decided at import.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import attention as _attention
from repro.kernels import bitserial as _bitserial
from repro.kernels import int8_matmul as _int8_matmul
from repro.kernels import mws as _mws
from repro.kernels import search as _search
from repro.kernels import shift_add as _shift_add


def _on_tpu(x) -> bool:
    return (isinstance(x, jax.Array) and not isinstance(x, jax.core.Tracer)
            and any(d.platform == "tpu" for d in x.devices()))


def _kernel_entry(*static: str):
    """jit ``fn`` with ``static`` + ``interpret`` static, and refuse
    interpret mode for inputs on a TPU (checked before tracing)."""
    def wrap(fn):
        jitted = jax.jit(fn, static_argnames=static + ("interpret",))

        @functools.wraps(fn)
        def call(*args, interpret: bool = False, **kw):
            if interpret and any(_on_tpu(a) for a in args):
                raise ValueError(f"{fn.__name__}: interpret mode asked for "
                                 "arrays on a TPU, where kernels run compiled")
            return jitted(*args, interpret=interpret, **kw)
        return call
    return wrap


def _pad_to(x, mult_rows, mult_cols):
    r, c = x.shape[-2:]
    pr = (-r) % mult_rows
    pc = (-c) % mult_cols
    if pr or pc:
        pad = [(0, 0)] * (x.ndim - 2) + [(0, pr), (0, pc)]
        x = jnp.pad(x, pad)
    return x, r, c


def _blk(dim: int, pref: int) -> int:
    """Largest power-of-two fraction of ``pref`` that divides ``dim``."""
    b = min(pref, dim)
    while dim % b:
        b //= 2
    return max(1, b)


@_kernel_entry("op")
def mws_bitwise(stack: jnp.ndarray, op: str = "and",
                interpret: bool = False) -> jnp.ndarray:
    """Bulk bitwise reduce of stacked pages (Flash-Cosmos MWS)."""
    assert stack.ndim == 3, "expected [n_ops, rows, cols]"
    assert jnp.issubdtype(stack.dtype, jnp.integer)
    padded, r, c = _pad_to(stack, 8, 128)
    out = _mws.mws_bitwise(padded, op=op, interpret=interpret)
    return out[:r, :c]


@_kernel_entry()
def bitserial_add(a: jnp.ndarray, b: jnp.ndarray,
                  interpret: bool = False) -> jnp.ndarray:
    assert a.shape == b.shape and a.dtype == b.dtype
    pa, r, c = _pad_to(a, 8, 128)
    pb, _, _ = _pad_to(b, 8, 128)
    return _bitserial.bitserial_add(pa, pb, interpret=interpret)[:r, :c]


@_kernel_entry()
def bitserial_mul(a: jnp.ndarray, b: jnp.ndarray,
                  interpret: bool = False) -> jnp.ndarray:
    assert a.shape == b.shape and a.dtype == b.dtype
    pa, r, c = _pad_to(a, 8, 128)
    pb, _, _ = _pad_to(b, 8, 128)
    return _bitserial.bitserial_mul(pa, pb, interpret=interpret)[:r, :c]


@_kernel_entry("bits")
def shift_add_mul(a: jnp.ndarray, b: jnp.ndarray, bits: int = 8,
                  interpret: bool = False) -> jnp.ndarray:
    assert a.shape == b.shape and a.dtype == b.dtype
    pa, r, c = _pad_to(a, 8, 128)
    pb, _, _ = _pad_to(b, 8, 128)
    return _shift_add.shift_add_mul(pa, pb, bits=bits,
                                    interpret=interpret)[:r, :c]


@_kernel_entry()
def int8_matmul(a: jnp.ndarray, b: jnp.ndarray,
                interpret: bool = False) -> jnp.ndarray:
    assert a.dtype == jnp.int8 and b.dtype == jnp.int8
    m, k = a.shape
    _, n = b.shape
    return _int8_matmul.int8_matmul(
        a, b, block_m=_blk(m, 128), block_n=_blk(n, 128),
        block_k=_blk(k, 128), interpret=interpret)


@_kernel_entry("causal")
def flash_attention(q, k, v, causal: bool = True,
                    interpret: bool = False) -> jnp.ndarray:
    return _attention.flash_attention(
        q, k, v, causal=causal,
        block_q=_blk(q.shape[1], 512), block_k=_blk(k.shape[1], 512),
        interpret=interpret)


@_kernel_entry()
def search_pages(stack: jnp.ndarray, query: jnp.ndarray,
                 interpret: bool = False) -> jnp.ndarray:
    """In-flash exact-match search (§7 extensibility kernel)."""
    assert stack.ndim == 2 and query.ndim == 1
    padded, r, c = _pad_to(stack, 8, stack.shape[1])
    return _search.search_pages(padded, query, interpret=interpret)[:r]
