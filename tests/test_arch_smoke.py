"""Per-architecture smoke tests (assignment requirement): every assigned
arch instantiates a REDUCED config of the same family and runs one forward
+ train step + decode step on CPU, asserting shapes and no NaNs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.launch.steps import build_serve_step, build_train_step
from repro.models import model as M
from repro.optim.adamw import adamw_init


def _extras(cfg, B, S, rng):
    kw = {}
    if cfg.frontend == "vision_patches":
        kw["extra_embeds"] = jnp.asarray(
            rng.normal(size=(B, 4, cfg.d_model)).astype(np.float32))
        kw["pos3"] = jnp.broadcast_to(jnp.arange(S + 4), (3, B, S + 4)
                                      ).astype(jnp.int32)
    if cfg.frontend == "audio_frames":
        kw["enc_feats"] = jnp.asarray(
            rng.normal(size=(B, 6, cfg.d_model)).astype(np.float32))
    return kw


# The per-arch matrix compiles ~10 reduced models (several minutes of XLA
# time): slow tier — even one reduced model compiles for 10+ s on a small
# CPU box.  The fast tier's model canary is
# test_models.py::test_decode_matches_full_forward[tinyllama-1.1b].
@pytest.mark.slow
@pytest.mark.parametrize("arch", configs.ARCHS)
def test_smoke_forward_train_decode(arch):
    cfg = configs.get(arch).reduced()
    assert cfg.family == configs.get(arch).family
    rng = np.random.default_rng(0)
    B, S = 2, 8
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, (B, S), dtype=np.int32))
    labels = jnp.asarray(rng.integers(0, cfg.vocab, (B, S), dtype=np.int32))
    kw = _extras(cfg, B, S, rng)

    # forward + loss
    loss = M.lm_loss(cfg, params, tokens, labels, **kw)
    assert np.isfinite(float(loss)), f"{arch}: non-finite loss"

    # one full optimizer step
    batch = {"tokens": tokens, "labels": labels, **kw}
    step = build_train_step(cfg, total_steps=10)
    new_params, opt, metrics = step(params, adamw_init(params), batch)
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))
    changed = jax.tree_util.tree_map(
        lambda a, b: float(jnp.abs(a.astype(jnp.float32)
                                   - b.astype(jnp.float32)).max()),
        params, new_params)
    assert max(jax.tree_util.tree_leaves(changed)) > 0, \
        f"{arch}: parameters did not change"

    # prefill + decode
    caches = M.init_cache(cfg, B, S + 4)
    logits, caches = M.prefill(cfg, params, tokens, caches, **kw)
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    serve = build_serve_step(cfg)
    tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
    lg, caches = serve(params, caches, tok, jnp.int32(S))
    assert lg.shape == (B, cfg.vocab)
    assert np.isfinite(np.asarray(lg, np.float32)).all(), f"{arch}: NaN logits"


@pytest.mark.parametrize("arch", configs.ARCHS)
def test_exact_published_config(arch):
    """The full (non-reduced) config matches the assigned numbers."""
    cfg = configs.get(arch)
    expected = {
        "minicpm-2b": (40, 2304, 36, 36, 5760, 122753),
        "tinyllama-1.1b": (22, 2048, 32, 4, 5632, 32000),
        "qwen3-4b": (36, 2560, 32, 8, 9728, 151936),
        "stablelm-1.6b": (24, 2048, 32, 32, 5632, 100352),
        "dbrx-132b": (40, 6144, 48, 8, 10752, 100352),
        "deepseek-v2-236b": (60, 5120, 128, 128, 1536, 102400),
        "zamba2-1.2b": (38, 2048, 32, 32, 8192, 32000),
        "seamless-m4t-medium": (12, 1024, 16, 16, 4096, 256206),
        "qwen2-vl-2b": (28, 1536, 12, 2, 8960, 151936),
        "xlstm-125m": (12, 768, 4, 4, 0, 50304),
    }[arch]
    got = (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
           cfg.d_ff, cfg.vocab)
    assert got == expected


def test_moe_configs_exact():
    dbrx = configs.get("dbrx-132b")
    assert dbrx.moe and dbrx.n_experts == 16 and dbrx.experts_per_tok == 4
    ds = configs.get("deepseek-v2-236b")
    assert ds.moe and ds.n_experts == 160 and ds.experts_per_tok == 6
    assert ds.n_shared_experts == 2
    assert ds.mla and ds.kv_lora_rank == 512

