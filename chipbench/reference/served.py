"""The check of a served model: how far below the reference's best logit
each served token lies.

For a request served greedily, every served token should be the reference's
first choice at its position, or within rounding of it. The gap of a token
is ``max(reference logits) - reference logit of the token``, read at the
position that produced it: the prompt's last position for the token that
prefill produced, the previous token's position for each decoded one.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import dense_gqa as R

ROWS = 256      # positions whose float32 logits are held at once
BLOCK = 4       # requests whose float32 attention is held at once


@functools.lru_cache(maxsize=None)
def _fns(items, control: bool):
    c = dict(items)
    mul = R.fp8_mm if control else R.mm

    @jax.jit
    def hidden(w, seqs):
        return R.hidden(c, w, seqs, mul)

    @jax.jit
    def ref_gap(w, h, targets):
        lg = R.logits(c, w, h)
        best = jnp.max(lg, axis=-1)
        return best - jnp.take_along_axis(lg, targets[..., None], -1)[..., 0]

    @jax.jit
    def control_gap(w, h, hc):
        lg = R.logits(c, w, h)
        pick = jnp.argmax(R.logits(c, w, hc, R.fp8_mm), axis=-1)
        best = jnp.max(lg, axis=-1)
        return best - jnp.take_along_axis(lg, pick[..., None], -1)[..., 0]

    return hidden, ref_gap, control_gap


def token_gaps(shape: dict, w, seqs: np.ndarray, prompt_len: int,
               control: bool = False) -> np.ndarray:
    """seqs [R, S]: each request's prompt and then its served tokens.
    Returns the gap of every served token [R, S - prompt_len]; with
    ``control``, the gap of the token that the fp8 model puts first at the
    same positions instead. Runs ``BLOCK`` requests at a time."""
    items = tuple(sorted(shape.items()))
    hidden, ref_gap, control_gap = _fns(items, False)
    fp8_hidden = _fns(items, True)[0]
    out = []
    for r in range(0, len(seqs), BLOCK):
        seqs_d = jnp.asarray(seqs[r:r + BLOCK], jnp.int32)
        h = hidden(w, seqs_d)
        hc = fp8_hidden(w, seqs_d) if control else None
        row = []
        for lo in range(prompt_len - 1, seqs.shape[1] - 1, ROWS):
            hi = min(lo + ROWS, seqs.shape[1] - 1)
            if control:
                g = control_gap(w, h[:, lo:hi], hc[:, lo:hi])
            else:
                g = ref_gap(w, h[:, lo:hi], seqs_d[:, lo + 1:hi + 1])
            row.append(np.asarray(g))
        out.append(np.concatenate(row, axis=1))
        del h, hc
    return np.concatenate(out, axis=0)
