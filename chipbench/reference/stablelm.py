"""Plain float32 reference of StableLM 2 (``StableLmForCausalLM``), its
training loss and the gradients of that loss, independent of the program
(it imports nothing of ``repro``).

The model as the published ``config.json`` of stabilityai/stablelm-2-1_6b
gives it: each block is pre-norm and sequential (``use_parallel_residual``
false), ``x + attn(LN1(x))`` then ``x + mlp(LN2(x))``, LayerNorm with
weight and bias (``layer_norm_eps``); q, k and v projections with biases
(``use_qkv_bias``), the output projection without; no q/k norm
(``qk_layernorm`` false); rotary embedding over the first
``rotary_fraction`` of each head's dims (HF's ``rotary_ndims``, the two
halves of that part rotated as pairs at frequencies
``theta ** (-2i / rotary_ndims)``), the rest passed through; causal
softmax attention (grouped where ``n_kv_heads < n_heads``); the SwiGLU MLP
``(silu(x w1) * (x w3)) w2``; a final LayerNorm and a separate
unembedding. Dropout is 0 in the published model and absent here.

The loss is the program's ``lm_loss``: the mean over every position of
``logsumexp(logits) - logit of the label``.

Weights are a dict ``{"emb", "unemb", "ln_f", "ln_f_b", "layers"}``, the
layers' leaves (``ln1``, ``ln1_b``, ``wq``, ``bq``, ``wk``, ``bk``,
``wv``, ``bv``, ``wo``, ``ln2``, ``ln2_b``, ``w1``, ``w3``, ``w2``)
stacked on a leading layer axis, matrices as [in, out]. Every product runs
in float32 at ``Precision.HIGHEST``; ``mul`` may be ``fp8_mm``, the same
model with every product's operands rounded to fp8 in the forward and the
backward pass, the precision below the configuration's bfloat16: the
control of the check.

At full width it works in blocks: ``per`` sequences at a time with their
gradients summed, each layer rematerialized in the backward pass, attention
``QBLOCK`` queries at a time and the loss ``LBLOCK`` positions at a time,
each recomputed in the backward pass.
"""
from __future__ import annotations

import functools
from typing import Callable, Mapping

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import dense_gqa as G

F32 = jnp.float32
QBLOCK = 512    # queries whose float32 scores are held at once
LBLOCK = 512    # positions whose float32 logits are held at once

mm = G.mm


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def fp8_mm(spec: str, a, b):
    """einsum with both operands rounded to fp8 (one scale per tensor); its
    backward pass rounds the incoming gradient and the saved operands
    alike, as an fp8 training path does."""
    return G.fp8_mm(spec, a, b)


def _fp8_fwd(spec, a, b):
    return G.fp8_mm(spec, a, b), (a, b)


def _fp8_bwd(spec, saved, g):
    a, b = saved
    _, vjp = jax.vjp(lambda x, y: mm(spec, x, y), G._fp8(a), G._fp8(b))
    da, db = vjp(G._fp8(g))
    return da.astype(a.dtype), db.astype(b.dtype)


fp8_mm.defvjp(_fp8_fwd, _fp8_bwd)


def head_dim(c: Mapping) -> int:
    return c.get("d_head") or c["d_model"] // c["n_heads"]


def layernorm(x, g, b, eps):
    x = x.astype(F32)
    x = x - jnp.mean(x, -1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(F32) + b.astype(F32)


def rope(x, theta, fraction):
    """x [R, S, H, dh] at positions 0..S-1: the first ``int(dh *
    fraction)`` dims rotated, the rest passed through."""
    s, dh = x.shape[1], x.shape[-1]
    rot = int(dh * fraction)
    inv = 1.0 / (theta ** (np.arange(0, rot, 2, dtype=np.float64) / rot))
    ang = np.arange(s, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), F32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), F32)[None, :, None, :]
    x1, x2 = jnp.split(x[..., :rot], 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rot:]], -1)


def attention(q, k, v, mul: Callable):
    """Causal attention; q [R, S, Hkv, G, dh], k and v [R, S, Hkv, dh]
    -> [R, S, Hkv, G, dh], ``QBLOCK`` queries at a time."""
    s, dh = q.shape[1], q.shape[-1]
    qb = min(QBLOCK, s)

    @jax.checkpoint
    def one(args):
        q_blk, lo = args
        scores = mul("rqhgd,rkhd->rhgqk", q_blk, k) / np.sqrt(dh)
        qpos = lo + jnp.arange(qb)[:, None]
        scores = jnp.where(qpos >= jnp.arange(s)[None, :], scores, -jnp.inf)
        return mul("rhgqk,rkhd->rqhgd", jax.nn.softmax(scores, axis=-1), v)

    out = jax.lax.map(one, (_blocks(q, qb), jnp.arange(0, s, qb)))
    return jnp.moveaxis(out, 0, 1).reshape(q.shape)


def _blocks(x, size):
    """x [R, S, ...] -> [S / size, R, size, ...]."""
    r, s = x.shape[:2]
    if s % size:
        raise ValueError(f"sequence {s} is not a multiple of {size}")
    return jnp.moveaxis(x.reshape(r, s // size, size, *x.shape[2:]), 1, 0)


def block(c: Mapping, w, x, mul: Callable = mm):
    """One decoder block over x [R, S, D] float32."""
    r, s, d = x.shape
    h_, kv, dh = c["n_heads"], c["n_kv_heads"], head_dim(c)
    eps = c["norm_eps"]
    h = layernorm(x, w["ln1"], w["ln1_b"], eps)
    q = (mul("rsd,de->rse", h, w["wq"]) + w["bq"].astype(F32)
         ).reshape(r, s, h_, dh)
    k = (mul("rsd,de->rse", h, w["wk"]) + w["bk"].astype(F32)
         ).reshape(r, s, kv, dh)
    v = (mul("rsd,de->rse", h, w["wv"]) + w["bv"].astype(F32)
         ).reshape(r, s, kv, dh)
    frac = c.get("rotary_fraction", 1.0)
    q = rope(q, c["rope_theta"], frac).reshape(r, s, kv, h_ // kv, dh)
    k = rope(k, c["rope_theta"], frac)
    att = attention(q, k, v, mul).reshape(r, s, h_ * dh)
    x = x + mul("rse,ed->rsd", att, w["wo"])
    h = layernorm(x, w["ln2"], w["ln2_b"], eps)
    up = jax.nn.silu(mul("rsd,df->rsf", h, w["w1"])) \
        * mul("rsd,df->rsf", h, w["w3"])
    return x + mul("rsf,fd->rsd", up, w["w2"])


def hidden(c: Mapping, w, tokens, mul: Callable = mm):
    """Final normalized hidden states [R, S, D] float32; each layer is
    recomputed in the backward pass."""
    x = jnp.take(w["emb"], tokens, axis=0).astype(F32)
    step = jax.checkpoint(lambda x, wl: block(c, wl, x, mul))
    x, _ = jax.lax.scan(lambda x, wl: (step(x, wl), None), x, w["layers"])
    return layernorm(x, w["ln_f"], w["ln_f_b"], c["norm_eps"])


def logits(c: Mapping, w, tokens, mul: Callable = mm):
    """[R, S, V] float32 logits of every position (small sizes)."""
    return mul("rsd,dv->rsv", hidden(c, w, tokens, mul), w["unemb"])


def nll_sum(c: Mapping, w, tokens, labels, mul: Callable = mm):
    """Sum over every position of ``logsumexp(logits) - logit of the
    label``; the logits ``LBLOCK`` positions at a time."""
    h = hidden(c, w, tokens, mul)
    lb = min(LBLOCK, h.shape[1])

    @jax.checkpoint
    def part(args):
        h_blk, lab = args
        lg = mul("rsd,dv->rsv", h_blk, w["unemb"])
        gold = jnp.take_along_axis(lg, lab[..., None], -1)[..., 0]
        return jnp.sum(jax.nn.logsumexp(lg, axis=-1) - gold)

    return jnp.sum(jax.lax.map(part, (_blocks(h, lb), _blocks(labels, lb))))


def loss_and_grads(c: Mapping, w, tokens, labels, mul: Callable = mm,
                   per: int = 1, jit: Callable = jax.jit):
    """(loss, gradients of the loss with respect to every leaf of ``w``,
    float32), the loss as ``lm_loss`` gives it for tokens and labels
    [B, S]: ``per`` sequences at a time through ``jit`` (which may place
    the computation), their sums of losses and gradients added up."""
    with jax.default_matmul_precision("highest"):
        fn = jit(jax.value_and_grad(
            lambda w_, t, l: nll_sum(c, w_, t, l, mul)))
        total, grads = 0.0, None
        for lo in range(0, tokens.shape[0], per):
            val, g = fn(w, tokens[lo:lo + per], labels[lo:lo + per])
            total = total + val
            grads = g if grads is None else jax.tree_util.tree_map(
                jnp.add, grads, g)
    n = tokens.shape[0] * tokens.shape[1]
    return total / n, jax.tree_util.tree_map(lambda g: g / n, grads)
