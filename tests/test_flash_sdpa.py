"""The attention path of ``models/layers._sdpa``.

On the TPU, long self-attention runs through the Pallas flash kernel and
its backward (``_flash_sdpa``); everywhere else, and at the shapes the
kernel does not take, through the jnp q-block scan. The CPU has no Mosaic
compiler, so the kernel path runs here under TPU interpret mode and is
held to the jnp attention in float32, its output and its q/k/v gradients;
``tests/test_tpu_compile.py`` checks that a v5e program takes it.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu import flash_attention as FA

from repro.kernels.attention import flash_mha
from repro.launch.mesh import make_mesh
from repro.launch.train import on_mesh
from repro.models import layers as L

S = 2 * L.SDPA_CHUNK      # over SDPA_CHUNK and a whole number of tiles


def _qkvg(b, sq, sk, h, dh, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (b, sq, h, dh), jnp.float32)
    k, v = (jax.random.normal(kk, (b, sk, h, dh), jnp.float32)
            for kk in ks[1:3])
    g = jax.random.normal(ks[3], (b, sq, h, dh), jnp.float32)
    return q, k, v, g


def _out_and_grads(attend, q, k, v, g):
    out, vjp = jax.vjp(attend, q, k, v)
    return (out,) + vjp(g)


def _assert_close(got, want):
    """Output, dq, dk, dv each within 1e-5 of the largest element of the
    jnp attention's (the kernel reads at most 1.2e-6 here, the planted
    mask 1.0)."""
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        gap = float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
        assert gap < 1e-5, (name, gap)


# (causal, dh, B, H, S, on a mesh, planted): 2 x 2 tiles of 1024 at S 2048,
# 3 x 3 of 512 at 1536 (``L.FLASH_BLOCKS``), one tile at 1024 (the forward
# with no running statistics); ``planted`` runs the kernel with no causal
# mask against the causal jnp attention, which must fail
CASES = [(True, 64, 2, 2, 2048, False, False),
         (True, 64, 1, 2, 1024, False, False),
         (False, 64, 2, 2, 2048, False, False),
         (True, 128, 2, 3, 1536, False, False),
         (False, 128, 3, 2, 1536, False, False),
         (True, 64, 2, 2, 1536, True, False),
         (True, 64, 2, 2, 2048, False, True)]


@pytest.mark.parametrize(
    "causal, dh, b, h, s, meshed, planted", CASES,
    ids=[f"{'causal' if c else 'full'}-dh{d}-b{b}-h{h}-s{s}"
         f"{'-mesh' if m else ''}{'-planted' if p else ''}"
         for c, d, b, h, s, m, p in CASES])
def test_flash_path_matches_jnp_sdpa(causal, dh, b, h, s, meshed, planted):
    q, k, v, g = _qkvg(b, s, s, h, dh)
    assert L._flash_fits(q, k)
    kernel_causal = causal and not planted
    flash = lambda q, k, v: L._flash_sdpa(q, k, v, kernel_causal)
    mesh = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1]) \
        if meshed else None
    with on_mesh(mesh), pltpu.force_tpu_interpret_mode():
        got = _out_and_grads(flash, q, k, v, g)
    want = _out_and_grads(lambda q, k, v: L._sdpa_jnp(q, k, v, causal),
                          q, k, v, g)
    if planted:
        with pytest.raises(AssertionError):
            _assert_close(got, want)
    else:
        _assert_close(got, want)


@pytest.mark.parametrize("sq, sk", [(L.SDPA_CHUNK, L.SDPA_CHUNK),
                                    (S, S - L.SDPA_CHUNK // 2), (S, S)],
                         ids=["short", "cross", "long"])
def test_cpu_lowers_the_jnp_attention(sq, sk):
    """Without interpret mode the CPU runs the jnp attention: for short
    and cross lengths ``_sdpa`` traces to the jnp program itself; for long
    self-attention only the jnp branch is lowered, and it computes what
    the jnp attention does, bit for bit."""
    q, k, v, _ = _qkvg(2, sq, sk, 2, 64)
    causal = sq == sk
    sdpa = jax.jit(lambda q, k, v: L._sdpa(q, k, v, causal))
    jnp_sdpa = jax.jit(lambda q, k, v: L._sdpa_jnp(q, k, v, causal))
    assert L._flash_fits(q, k) == (sq == sk == S)
    if sq != S or sk != S:
        assert str(jax.make_jaxpr(sdpa)(q, k, v)) == \
            str(jax.make_jaxpr(jnp_sdpa)(q, k, v))
    lowered = sdpa.lower(q, k, v).as_text()
    assert "tpu_custom_call" not in lowered and "pallas" not in lowered
    np.testing.assert_array_equal(np.asarray(sdpa(q, k, v)),
                                  np.asarray(jnp_sdpa(q, k, v)))


def _bf16_jnp_dk(q, k, v, do):
    """dk of causal attention over [B,H,S,dh] as the jnp attention gives it on
    a TPU: bf16 operands into every product, float32 sums, dk stored in
    bf16 (the rounding written out, since the CPU multiplies float32
    operands whole)."""
    r = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)
    q, k, v, do = map(r, (q, k, v, do))
    s = q.shape[2]
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    p = jax.nn.softmax(jnp.where(jnp.tri(s, dtype=bool), logits, -1e30), -1)
    dp = jnp.einsum("bhqd,bhkd->bhqk", do, v)
    ds = p * (dp - jnp.sum(p * dp, -1, keepdims=True)) / np.sqrt(q.shape[-1])
    return r(jnp.einsum("bhqk,bhqd->bhkd", r(ds), q))


@pytest.mark.parametrize("backward", ["flash_mha", "library"])
def test_key_bias_gradient_stays_at_rounding(backward):
    """A bias added to every key shifts each query's logits by one constant,
    which the softmax cancels: its gradient, the sum of dk over positions,
    is zero in exact arithmetic, and bf16 leaves rounding there, which
    AdamW scales up to a full step. With its row sums taken from the
    float32 output, the kernel path leaves no more than the jnp attention
    does on a TPU (0.92-1.05x of it here); the library's own backward,
    whose row sums come from the output rounded to bf16, leaves 2.2x at
    this mean of q, and must fail."""
    b, h, s, dh = 1, 2, 2 * L.SDPA_CHUNK, 64
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    q = (jax.random.normal(ks[0], (b, h, s, dh)) + 3.0).astype(jnp.bfloat16)
    k = (2 * jax.random.normal(ks[1], (b, h, s, dh))).astype(jnp.bfloat16)
    v, do = (jax.random.normal(kk, (b, h, s, dh)).astype(jnp.bfloat16)
             for kk in ks[2:])
    block = L.SDPA_CHUNK
    if backward == "flash_mha":
        attend = lambda q, k, v: flash_mha(q, k, v, True, block)
    else:
        sizes = FA.BlockSizes(*(block,) * 3, 1, *(block,) * 7)  # block_b 1
        attend = lambda q, k, v: FA.flash_attention(
            q, k, v, causal=True, sm_scale=1 / np.sqrt(dh),
            block_sizes=sizes)
    with pltpu.force_tpu_interpret_mode():
        dk = jax.vjp(attend, q, k, v)[1](do)[1]
    bias_grad = lambda dk: float(jnp.linalg.norm(
        jnp.sum(dk.astype(jnp.float32), axis=2)))
    ratio = bias_grad(dk) / bias_grad(_bf16_jnp_dk(q, k, v, do))
    if backward == "library":
        assert ratio > 1.25, ratio
    else:
        assert ratio < 1.25, ratio
