"""Plain float32 reference of a dense GQA decoder, independent of the
program (it imports nothing of ``repro``).

It follows the ``ArchConfig`` semantics of the repo's dense blocks: RMSNorm
before attention and MLP, optional RMSNorm over each q and k head
(``qk_norm``), rotary embedding over the whole head with the two halves of
each head rotated as pairs, causal grouped-query softmax attention, a SwiGLU
MLP (``silu(x w1) * (x w3)) w2``), a final RMSNorm and a tied or separate
unembedding. Departures of that from the published models are listed in
each configuration file.

Weights are drawn from the seed with the same stream of random numbers the
program's initializer uses (``init_weights``), in bfloat16 as served; every
product runs in float32 at ``Precision.HIGHEST``. ``mm`` may be replaced by
``fp8_mm`` to compute the same model with operands rounded to fp8, the
precision below the configurations' bfloat16: the control of the check.
"""
from __future__ import annotations

import functools
from typing import Callable, Mapping

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def mm(spec: str, a, b):
    """einsum in float32 at full precision."""
    return jnp.einsum(spec, a.astype(F32), b.astype(F32), precision=HIGHEST)


def _fp8(x):
    """x rounded to float8_e4m3fn with one scale for the tensor, as an
    fp8 matmul path scales its operands."""
    x = x.astype(F32)
    scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(jnp.float8_e4m3fn).astype(F32) / scale


def fp8_mm(spec: str, a, b):
    return jnp.einsum(spec, _fp8(a), _fp8(b), precision=HIGHEST)


# -- weights from the seed ----------------------------------------------------

def head_dim(c: Mapping) -> int:
    return c.get("d_head") or c["d_model"] // c["n_heads"]


def _normal(key, shape, scale, dtype):
    w = jax.lax.optimization_barrier(jax.random.normal(key, shape, F32))
    return (w * scale).astype(dtype)


def _dense(key, d_in, d_out, dtype, scale=None):
    return _normal(key, (d_in, d_out),
                   scale if scale is not None else 1.0 / np.sqrt(d_in), dtype)


def _layer(key, c, dtype):
    d, dh, f = c["d_model"], head_dim(c), c["d_ff"]
    k_attn, k_mlp, _, _ = jax.random.split(key, 4)
    ka = jax.random.split(k_attn, 6)
    km = jax.random.split(k_mlp, 3)
    w = {"ln1": jnp.ones((d,), dtype), "ln2": jnp.ones((d,), dtype),
         "wq": _dense(ka[0], d, c["n_heads"] * dh, dtype),
         "wk": _dense(ka[1], d, c["n_kv_heads"] * dh, dtype),
         "wv": _dense(ka[2], d, c["n_kv_heads"] * dh, dtype),
         "wo": _dense(ka[3], c["n_heads"] * dh, d, dtype),
         "w1": _dense(km[0], d, f, dtype),
         "w3": _dense(km[1], d, f, dtype),
         "w2": _dense(km[2], f, d, dtype)}
    if c.get("qk_norm"):
        w["q_norm"] = jnp.ones((dh,), dtype)
        w["k_norm"] = jnp.ones((dh,), dtype)
    return w


def init_weights(c: Mapping, key):
    """Weights as the seed gives them, in the configuration's dtype: the
    embedding scaled by 0.02, every other matrix by 1/sqrt(fan_in), norms
    at one; layers stacked on a leading axis."""
    return _init(tuple(sorted(c.items())), key)


@functools.partial(jax.jit, static_argnums=0)
def _init(items, key):
    c = dict(items)
    dtype = jnp.dtype(c.get("dtype", "bfloat16"))
    keys = jax.random.split(key, 8)
    w = {"emb": _dense(keys[0], c["vocab"], c["d_model"], dtype, 0.02),
         "ln_f": jnp.ones((c["d_model"],), dtype)}
    if not c.get("tie_embeddings", True):
        w["unemb"] = _dense(keys[1], c["d_model"], c["vocab"], dtype)
    (layer_key,) = jax.random.split(keys[2], 1)
    w["layers"] = jax.vmap(lambda k: _layer(k, c, dtype))(
        jax.random.split(layer_key, c["n_layers"]))
    return w


# -- forward --------------------------------------------------------------------

def rmsnorm(x, g, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(F32)


def rope(x, theta):
    """x [R, S, H, dh] at positions 0..S-1."""
    s, dh = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, dh, 2, dtype=np.float64) / dh))
    ang = np.arange(s, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), F32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), F32)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def block(c: Mapping, w, x, mul: Callable = mm):
    """One decoder block over x [R, S, D] float32."""
    r, s, d = x.shape
    h_, kv, dh = c["n_heads"], c["n_kv_heads"], head_dim(c)
    eps = c["norm_eps"]
    h = rmsnorm(x, w["ln1"], eps)
    q = mul("rsd,de->rse", h, w["wq"]).reshape(r, s, h_, dh)
    k = mul("rsd,de->rse", h, w["wk"]).reshape(r, s, kv, dh)
    v = mul("rsd,de->rse", h, w["wv"]).reshape(r, s, kv, dh)
    if c.get("qk_norm"):
        q = rmsnorm(q, w["q_norm"], eps)
        k = rmsnorm(k, w["k_norm"], eps)
    q = rope(q, c["rope_theta"]).reshape(r, s, kv, h_ // kv, dh)
    k = rope(k, c["rope_theta"])
    scores = mul("rqhgd,rkhd->rhgqk", q, k) / np.sqrt(dh)
    causal = np.tril(np.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    att = mul("rhgqk,rkhd->rqhgd", probs, v).reshape(r, s, h_ * dh)
    x = x + mul("rse,ed->rsd", att, w["wo"])
    h = rmsnorm(x, w["ln2"], eps)
    up = jax.nn.silu(mul("rsd,df->rsf", h, w["w1"])) \
        * mul("rsd,df->rsf", h, w["w3"])
    return x + mul("rsf,fd->rsd", up, w["w2"])


def hidden(c: Mapping, w, tokens, mul: Callable = mm):
    """Final normalized hidden states [R, S, D] float32, layer by layer."""
    x = jnp.take(w["emb"], tokens, axis=0).astype(F32)
    x, _ = jax.lax.scan(lambda x, wl: (block(c, wl, x, mul), None), x,
                        w["layers"])
    return rmsnorm(x, w["ln_f"], c["norm_eps"])


def unembedding(c: Mapping, w):
    return w["emb"].T if c.get("tie_embeddings", True) else w["unemb"]


def logits(c: Mapping, w, h, mul: Callable = mm):
    return mul("rsd,dv->rsv", h, unembedding(c, w))

