"""Model-zoo correctness: cache-consistency (prefill+decode == full forward),
MoE routing laws, shapes/finiteness per family."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import model as M
from repro.models.config import ArchConfig


def _mk(arch, d_head=None):
    cfg = configs.get(arch).reduced()
    if d_head:
        cfg = dataclasses.replace(cfg, d_head=d_head)
    params = M.init_params(cfg, jax.random.PRNGKey(1))
    return cfg, params


# one cheap arch stays in the fast tier: as reduced (16-wide heads, K/V
# sliced per layer) and with 128-wide heads at a batch of each order the
# layer scan carries (1, 8) and at one it slices (3), ``L.kv_order``; the
# rest of the cache-consistency grid runs nightly
@pytest.mark.parametrize("arch, B, d_head", [
    pytest.param("tinyllama-1.1b", 2, None, id="tinyllama-1.1b"),
    pytest.param("tinyllama-1.1b", 1, 128, id="tinyllama-1.1b-batch1"),
    pytest.param("tinyllama-1.1b", 3, 128, id="tinyllama-1.1b-batch3"),
    pytest.param("tinyllama-1.1b", 8, 128, id="tinyllama-1.1b-batch8"),
    pytest.param("qwen3-4b", 2, None, marks=pytest.mark.slow, id="qwen3-4b"),
    pytest.param("deepseek-v2-236b", 2, None, marks=pytest.mark.slow,
                 id="deepseek-v2-236b"),
    pytest.param("zamba2-1.2b", 2, None, marks=pytest.mark.slow,
                 id="zamba2-1.2b"),
    pytest.param("xlstm-125m", 2, None, marks=pytest.mark.slow,
                 id="xlstm-125m"),
])
def test_decode_matches_full_forward(arch, B, d_head):
    """Prefill(s-1 tokens) + decode(token s-1) must reproduce the logits of
    a full forward over s tokens — validates KV caches, MLA latent caches,
    Mamba/xLSTM recurrent states and position handling."""
    cfg, params = _mk(arch, d_head)
    S = 8
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, (B, S), dtype=np.int32))

    # full forward logits at the last position
    x = M.embed(cfg, params, tokens)
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    h, _ = M.forward(cfg, params, x, pos)
    full_logits = M.logits_of(cfg, params, h)[:, -1]

    # prefill on the first S-1, then one decode step
    caches = M.init_cache(cfg, B, S + 4)
    _, caches = M.prefill(cfg, params, tokens[:, :-1], caches)
    dec_logits, _ = M.decode_step(cfg, params, tokens[:, -1], S - 1, caches)

    np.testing.assert_allclose(np.asarray(dec_logits),
                               np.asarray(full_logits),
                               atol=0.15, rtol=0.05)


@pytest.mark.parametrize("B", [1, 3, 8])
def test_prefill_then_decode_steps_match_full_forward(B):
    """Prefill, then 4 decode steps, each step's logits against a
    cache-free forward over the same tokens: a row written to the wrong
    place in the cache shows in a later step, not in the first. 128-wide
    heads: carried at 1 and 8, sliced at 3 (``L.kv_order``)."""
    cfg, params = _mk("tinyllama-1.1b", 128)
    P_, N = 6, 4
    S = P_ + N
    rng = np.random.default_rng(1)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, (B, S), dtype=np.int32))
    x = M.embed(cfg, params, tokens)
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    h, _ = M.forward(cfg, params, x, pos)
    full = M.logits_of(cfg, params, h)[:, P_ - 1:]

    caches = M.init_cache(cfg, B, S + 2)
    logits, caches = M.prefill(cfg, params, tokens[:, :P_], caches)
    got = [logits[:, -1]]
    for i in range(N):
        logits, caches = M.decode_step(cfg, params, tokens[:, P_ + i],
                                       P_ + i, caches)
        got.append(logits)
    np.testing.assert_allclose(np.asarray(jnp.stack(got, axis=1)),
                               np.asarray(full), atol=0.15, rtol=0.05)


@pytest.mark.slow
def test_moe_capacity_and_routing():
    cfg = configs.get("dbrx-132b").reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    from repro.models import layers as L
    x = jnp.asarray(np.random.default_rng(0).normal(
        size=(2, 8, cfg.d_model)).astype(np.float32))
    moe_p = params["segments"][0]["moe"]
    one = jax.tree_util.tree_map(lambda a: a[0], moe_p)
    y = L.moe_apply(one, cfg, x.astype(jnp.bfloat16))
    assert y.shape == x.shape
    assert np.isfinite(np.asarray(y, np.float32)).all()


@pytest.mark.slow
def test_moe_grads_flow():
    cfg = configs.get("deepseek-v2-236b").reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jnp.zeros((1, 8), jnp.int32)
    labels = jnp.ones((1, 8), jnp.int32)

    g = jax.grad(lambda p: M.lm_loss(cfg, p, tokens, labels))(params)
    moe_g = g["segments"][0]["moe"]["experts"]["w1"]
    assert np.isfinite(np.asarray(moe_g, np.float32)).all()
    router_g = g["segments"][0]["moe"]["router"]
    assert float(jnp.abs(router_g.astype(jnp.float32)).sum()) > 0.0


def test_mla_cache_is_compressed():
    """The MLA cache stores the low-rank latent, not full K/V heads."""
    cfg = configs.get("deepseek-v2-236b").reduced()
    caches = M.init_cache(cfg, batch=1, max_seq=16)
    leaf_names = set()
    jax.tree_util.tree_map_with_path(
        lambda p, l: leaf_names.add(str(p[-1].key)), caches[0])
    assert "latent" in leaf_names and "k" not in leaf_names
    latent = caches[0]["latent"]
    assert latent.shape[-1] == cfg.kv_lora_rank


@pytest.mark.slow
def test_zamba2_shared_attention_params_are_shared():
    cfg = configs.get("zamba2-1.2b").reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    assert "shared_attn" in params
    # sattn segments carry no parameters of their own
    segs = M.segments_of(cfg)
    for seg_p, (kind, _) in zip(params["segments"], segs):
        if kind == "sattn":
            assert seg_p is None


def test_sub_quadratic_flags():
    assert configs.get("zamba2-1.2b").sub_quadratic
    assert configs.get("xlstm-125m").sub_quadratic
    for a in ("tinyllama-1.1b", "qwen3-4b", "dbrx-132b",
              "deepseek-v2-236b", "seamless-m4t-medium", "qwen2-vl-2b"):
        assert not configs.get(a).sub_quadratic


@pytest.mark.slow
def test_qk_norm_changes_attention():
    cfg = configs.get("qwen3-4b").reduced()
    assert cfg.qk_norm
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    seg = params["segments"][0]
    assert "q_norm" in seg["attn"] and "k_norm" in seg["attn"]


@pytest.mark.slow
def test_encdec_uses_encoder():
    cfg = configs.get("seamless-m4t-medium").reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    B, S = 1, 6
    tokens = jnp.zeros((B, S), jnp.int32)
    labels = jnp.ones((B, S), jnp.int32)
    feats = jnp.asarray(np.random.default_rng(0).normal(
        size=(B, 4, cfg.d_model)), jnp.float32)
    l_with = M.lm_loss(cfg, params, tokens, labels, enc_feats=feats)
    l_without = M.lm_loss(cfg, params, tokens, labels,
                          enc_feats=jnp.zeros_like(feats))
    assert np.isfinite(float(l_with)) and np.isfinite(float(l_without))
    assert abs(float(l_with) - float(l_without)) > 1e-6


@pytest.mark.slow
def test_mrope_position_streams_matter():
    cfg = configs.get("qwen2-vl-2b").reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    B, S = 1, 6
    tokens = jnp.zeros((B, S), jnp.int32)
    labels = jnp.ones((B, S), jnp.int32)
    emb = jnp.asarray(np.random.default_rng(0).normal(
        size=(B, 2, cfg.d_model)), jnp.float32)
    p1 = jnp.zeros((3, B, S + 2), jnp.int32)
    p2 = jnp.stack([jnp.arange(S + 2)[None].repeat(B, 0)] * 3)
    l1 = M.lm_loss(cfg, params, tokens, labels, extra_embeds=emb, pos3=p1)
    l2 = M.lm_loss(cfg, params, tokens, labels, extra_embeds=emb, pos3=p2)
    assert abs(float(l1) - float(l2)) > 1e-6


def test_embedding_gradient_sums_in_float32():
    """The gather's transpose adds every position's cotangent of a token
    into its row in float32: here one id fills a seventh of 4096
    positions, whose bf16 running sum would be about 8% off."""
    cfg = configs.get("tinyllama-1.1b").reduced()
    table = (0.02 * jax.random.normal(jax.random.PRNGKey(0), (64, 32))
             ).astype(jnp.bfloat16)
    tokens = jnp.zeros((4, 1024), jnp.int32).at[:, ::7].set(3)
    g = jax.random.normal(jax.random.PRNGKey(1), (4, 1024, 32)
                          ).astype(jnp.bfloat16)
    _, vjp = jax.vjp(lambda t: M.embed(cfg, {"emb": t}, tokens), table)
    got = np.asarray(vjp(g)[0].astype(jnp.float32), np.float64)
    want = np.zeros((64, 32))
    np.add.at(want, np.asarray(tokens), np.asarray(g.astype(jnp.float32)))
    # only the result's rounding to bf16 is left: under 2**-8 relative
    assert np.linalg.norm(got - want) / np.linalg.norm(want) < 4e-3
