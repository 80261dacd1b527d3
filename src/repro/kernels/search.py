"""In-flash exact-match search as a Pallas kernel (paper §7 extensibility).

The paper names search as a natural Conduit extension (Search-in-Memory /
TCAM-SSD class works): matching a query word against every stored word of a
page reduces to XNOR(query, word) followed by an all-bits AND — both MWS
primitives.  TPU adaptation: the records are laid out vertically, one plane
per word position (``[words_per_rec, rows, records]``), so each broadcast
query word XNORs against a whole (sublane x lane) tile and the record match
is an AND across planes, all in one pass (no HBM round-trips between the
XNOR and the reduction, mirroring in-array match lines).

``search_pages(stack[n_pages, words], query[words_per_rec]) -> match
bitmap [n_pages, records]`` where each record is ``words_per_rec``
consecutive int32 words.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _search_kernel(query_ref, planes_ref, out_ref, *, words_per_rec: int):
    match = None
    for j in range(words_per_rec):              # one word plane at a time
        xnor = ~(planes_ref[j] ^ query_ref[j])  # all-ones where bits equal
        eq = xnor == -1                         # word equality
        match = eq if match is None else match & eq
    out_ref[...] = match                        # record match bitmap


def search_pages(stack: jnp.ndarray, query: jnp.ndarray,
                 block_rows: int = 8, interpret: bool = False) -> jnp.ndarray:
    """Exact-match search of ``query`` against record-structured pages."""
    rows, words = stack.shape
    (wpr,) = query.shape
    assert words % wpr == 0, (words, wpr)
    recs = words // wpr
    block_rows = min(block_rows, rows)
    assert rows % block_rows == 0
    planes = stack.reshape(rows, recs, wpr).transpose(2, 0, 1)
    return pl.pallas_call(
        functools.partial(_search_kernel, words_per_rec=wpr),
        grid=(rows // block_rows,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((wpr, block_rows, recs), lambda i: (0, i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, recs), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, recs), jnp.bool_),
        interpret=interpret,
    )(query, planes)
