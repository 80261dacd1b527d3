"""The decode step's attention over the K/V cache.

On the TPU, ``models/layers.gqa_attention`` attends one query position per
sequence over the stacked cache with the Pallas kernel
``kernels/attention.decode_gqa_attention`` where ``_decode_fits`` holds; the
jnp path (``_cached_attention``) runs everywhere else. The CPU has no Mosaic
compiler, so the kernel runs here in interpret mode against the jnp path,
with the cache's rows past the write position poisoned: none may reach the
output. ``tests/test_tpu_compile.py`` checks that a v5e program takes it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.kernels.attention import decode_gqa_attention, kv_blocks_read
from repro.models import layers as L

BLOCK, MAX_SEQ, LAYERS = 16, 48, 2


def _cache(batch, heads, kv_heads, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (batch, 1, heads, 128), jnp.bfloat16)
    stack = (LAYERS, MAX_SEQ, kv_heads, batch, 128)
    k, v = (jax.random.normal(kk, stack, jnp.bfloat16) for kk in ks[1:])
    return q, k, v


def _poison(a, index, value):
    pos = jnp.arange(MAX_SEQ)[None, :, None, None, None]
    return jnp.where(pos > index, jnp.asarray(value, a.dtype), a)


# (batch, query heads, KV heads, index, K and V past the index): groups of
# 4 query heads and MHA, batch 8 and 16, the write position at the first
# position, either side of a block's edge and at the cache's end
CASES = [(b, h, hkv, index, poison)
         for b in (8, 16) for h, hkv in ((8, 2), (4, 4))
         for index, poison in ((0, (np.nan, np.nan)),
                               (BLOCK - 1, (1e4, np.nan)),
                               (BLOCK, (np.nan, -1e4)),
                               (BLOCK + 1, (-1e4, 1e4)),
                               (MAX_SEQ - 1, (np.nan, np.nan)))]


@pytest.mark.parametrize("batch, heads, kv_heads, index, poison", CASES)
def test_decode_kernel_matches_the_jnp_path(batch, heads, kv_heads, index,
                                            poison):
    """The kernel over a cache whose rows past ``index`` hold NaN or
    +-1e4 matches the jnp path over the clean cache to bf16 rounding
    (both take bf16 products with float32 accumulation; the online
    softmax rounds its unnormalized weights), and stays finite."""
    q, k, v = _cache(batch, heads, kv_heads)
    got = decode_gqa_attention(q[:, 0], _poison(k, index, poison[0]),
                               _poison(v, index, poison[1]), 1, index,
                               block=BLOCK, interpret=True)
    want = L._cached_attention(q, k, v, 1, index, "shbd")[:, 0]
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=2e-2, rtol=2e-2)


def test_decode_kernel_reads_the_given_layer():
    """Each layer of the stack is attended alone: another layer's rows
    give another output."""
    q, k, v = _cache(8, 8, 2)
    at = lambda layer: np.asarray(decode_gqa_attention(
        q[:, 0], k, v, layer, MAX_SEQ - 1, block=BLOCK, interpret=True),
        np.float32)
    want = np.asarray(L._cached_attention(q, k, v, 0, MAX_SEQ - 1,
                                          "shbd")[:, 0], np.float32)
    np.testing.assert_allclose(at(0), want, atol=2e-2, rtol=2e-2)
    assert np.abs(at(1) - want).max() > 0.1


@pytest.mark.parametrize("block", [16, 128, 256, 512])
def test_kv_blocks_read_covers_the_filled_prefix(block):
    """ceil((index + 1) / block) blocks, the last holding ``index``,
    never more than the cache's blocks. Over the benchmark's batch (index
    256..1279 of 1280) the filled prefix averages 0.6 of the cache, and
    the blocks read exceed it by half a block on average."""
    seq = 1280
    for index in range(seq):
        n = kv_blocks_read(index, block)
        assert n == -(-(index + 1) // block)
        assert (n - 1) * block <= index < n * block
        assert n <= -(-seq // block)
    steps = range(256, seq)
    prefix = np.mean([(i + 1) / seq for i in steps])
    read = np.mean([kv_blocks_read(i, block) * block / seq for i in steps])
    assert prefix == pytest.approx(0.6, abs=1e-3)
    assert read - prefix == pytest.approx(block / 2 / seq, abs=1e-3)


def _q(batch, s, cfg):
    return jax.ShapeDtypeStruct((batch, s, cfg.n_heads, cfg.head_dim),
                                jnp.bfloat16)


@pytest.mark.parametrize("arch, batch", [("qwen3-4b", 16), ("qwen3-4b", 8),
                                         ("qwen3-4b", 64),
                                         ("llama2-7b", 16),
                                         ("qwen2-vl-2b", 8)])
def test_decode_fits_the_carried_decode_steps(arch, batch):
    """One position against a carried ``shbd`` cache of 128-wide heads at
    a batch of whole 8-row groups: the benchmark's qwen3-4b decode at 16
    among them."""
    cfg = configs.get(arch)
    assert L._decode_fits(_q(batch, 1, cfg), L.kv_order(cfg, batch))


@pytest.mark.parametrize("arch, batch, s", [("qwen3-4b", 16, 256),
                                            ("stablelm-1.6b", 16, 1),
                                            ("qwen3-4b", 1, 1),
                                            ("qwen3-4b", 4, 1),
                                            ("qwen3-4b", 12, 1)])
def test_decode_fits_nothing_else(arch, batch, s):
    """Not prefill, not the sliced ``bshd`` order (stablelm's 64-wide
    heads, a batch of 12), not ``bhsd`` at batch 1, nor a batch short of
    an 8-row group."""
    cfg = configs.get(arch)
    assert not L._decode_fits(_q(batch, s, cfg), L.kv_order(cfg, batch))


def test_decode_fits_no_mesh():
    """Under a mesh the jnp path stays: a ``pallas_call`` has no
    partitioning rule."""
    from repro.launch.mesh import make_mesh
    from repro.launch.train import on_mesh
    cfg = configs.get("qwen3-4b")
    q = _q(16, 1, cfg)
    with on_mesh(make_mesh((1, 1), ("data", "model"))):
        assert not L._decode_fits(q, "shbd")
    assert L._decode_fits(q, "shbd")
