"""StableLM 2 1.6B on the program's normal path: LayerNorm with bias, q/k/v
biases and partial rotary, against the plain float32 reference
(``chipbench/reference/stablelm.py``) on seeded random weights at a
reduced size; the fp8 control of the reference fails the same tolerances.

Tolerances, and why: the program keeps bf16 params and activations (a
relative rounding of 2^-8 a value) with float32 accumulation; its loss
over 256 positions lands within 1e-4 of the reference's (relative), its
global grad norm within 3e-3 and each leaf's gradient within 0.04 in
relative L2, the q/k/v biases the worst (measured on the CPU). The limits
leave 2-10 times that room. The fp8 control rounds every product's
operands to 3 mantissa bits, and its worst leaf lies at 0.15-0.45."""
import dataclasses
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from chipbench.drivers import train_steps as D  # noqa: E402
from chipbench.reference import stablelm as R  # noqa: E402
from repro import configs  # noqa: E402
from repro.data import SyntheticLM  # noqa: E402
from repro.launch.specs import params_shapes  # noqa: E402
from repro.models import layers as L  # noqa: E402
from repro.models import model as M  # noqa: E402

LOSS_GAP, NORM_GAP, LEAF_GAP = 1e-3, 1e-2, 0.08
LOGIT_GAP = 0.15      # largest |logit gap| over the logits' std
B, S = 4, 64
CONFIG = REPO / "chipbench" / "configs" / "stablelm-1.6b.json"
URL = ("https://huggingface.co/stabilityai/stablelm-2-1_6b/blob/main/"
       "config.json")


def _cfg():
    cfg = configs.get("stablelm-1.6b").reduced()
    return dataclasses.replace(cfg, n_layers=2, block_pattern=(), remat=True)


def _shape(cfg) -> dict:
    return {k: getattr(cfg, k) for k in (
        "n_layers", "d_model", "n_heads", "n_kv_heads", "d_head", "d_ff",
        "vocab", "norm_eps", "rope_theta", "rotary_fraction")}


@pytest.fixture(scope="module")
def model():
    """The reduced model with random norms and biases (they start at one
    and zero), its reference weights and a batch."""
    cfg = _cfg()
    params = M.init_params(cfg, jax.random.PRNGKey(3))
    leaves, tdef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for i, (path, a) in enumerate(leaves):
        name = str(path[-1].key)
        noise = jax.random.normal(jax.random.fold_in(jax.random.PRNGKey(9),
                                                     i), a.shape)
        if name.endswith("_b") or name in ("bq", "bk", "bv"):
            a = (0.1 * noise).astype(a.dtype)
        elif name.startswith("ln"):
            a = (1 + 0.1 * noise).astype(a.dtype)
        out.append(a)
    params = jax.tree_util.tree_unflatten(tdef, out)
    batch = SyntheticLM(cfg.vocab, S, B, seed=5).batch(0)
    return cfg, params, D.reference_weights(params), batch


@pytest.fixture(scope="module")
def reference(model, monkeypatch_module):
    cfg, _, w, batch = model
    # blocks smaller than the sequence, as at full width
    monkeypatch_module.setattr(R, "QBLOCK", 16)
    monkeypatch_module.setattr(R, "LBLOCK", 32)
    loss, grads = R.loss_and_grads(_shape(cfg), w, batch["tokens"],
                                   batch["labels"], per=2)
    control = R.loss_and_grads(_shape(cfg), w, batch["tokens"],
                               batch["labels"], mul=R.fp8_mm, per=2)
    return (loss, D.by_name(grads)), (control[0], D.by_name(control[1]))


@pytest.fixture(scope="module")
def monkeypatch_module():
    mp = pytest.MonkeyPatch()
    yield mp
    mp.undo()


def _norm(grads: dict) -> float:
    return float(jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                              for g in grads.values())))


def test_loss_and_gradients_match_the_reference(model, reference):
    cfg, params, _, batch = model
    loss, grads = jax.value_and_grad(lambda p: M.lm_loss(
        cfg, p, batch["tokens"], batch["labels"]))(params)
    grads = D.by_name(D.layout(grads))
    (ref_loss, ref_grads), _ = reference
    got = D.gaps(loss, _norm(grads), grads, ref_loss, ref_grads)
    assert set(grads) == set(ref_grads) and len(grads) == 18
    assert got["train_loss_gap"] < LOSS_GAP
    assert got["grad_norm_gap"] < NORM_GAP
    assert got["grad_leaf_gap"] < LEAF_GAP


def test_the_fp8_control_fails(reference):
    (ref_loss, ref_grads), (c_loss, c_grads) = reference
    got = D.gaps(c_loss, _norm(c_grads), c_grads, ref_loss, ref_grads)
    assert got["grad_leaf_gap"] > LEAF_GAP


@pytest.mark.parametrize("mul, ok", [(R.mm, True), (R.fp8_mm, False)],
                         ids=["program", "fp8-control"])
def test_prefill_then_decode_match_the_reference_forward(model, mul, ok):
    """Prefill over 60 positions, then 3 decode steps through the cache:
    each step's logits against the reference's full forward pass (and
    the fp8 control's forward against the reference's, which fails)."""
    cfg, params, w, batch = model
    tokens = jnp.asarray(batch["tokens"])
    ref = R.logits(_shape(cfg), w, tokens)
    p_, n = S - 4, 3
    if ok:
        caches = M.init_cache(cfg, B, S)
        logits, caches = M.prefill(cfg, params, tokens[:, :p_], caches)
        got = [logits[:, -1]]
        for i in range(n):
            logits, caches = M.decode_step(cfg, params, tokens[:, p_ + i],
                                           p_ + i, caches)
            got.append(logits)
        got = jnp.stack(got, axis=1).astype(jnp.float32)
    else:
        got = R.logits(_shape(cfg), w, tokens, mul)[:, p_ - 1:p_ + n]
    ref = ref[:, p_ - 1:p_ + n]
    gap = float(jnp.max(jnp.abs(got - ref)) / jnp.std(ref))
    assert (gap < LOGIT_GAP) is ok


def test_partial_rotary():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 4, 64))
    pos = jnp.broadcast_to(jnp.arange(8), (2, 8))
    # fraction 1.0 is the whole-head rotary, the same program
    full = jax.make_jaxpr(lambda a: L.apply_rope(a, pos, 1e4))(x)
    one = jax.make_jaxpr(lambda a: L.apply_rope(a, pos, 1e4, 1.0))(x)
    assert str(full) == str(one)
    # a quarter: the first 16 dims rotated as a 16-wide head, the rest kept
    part = L.apply_rope(x, pos, 1e4, 0.25)
    np.testing.assert_array_equal(part[..., 16:], x[..., 16:])
    np.testing.assert_allclose(part[..., :16],
                               L.apply_rope(x[..., :16], pos, 1e4),
                               rtol=1e-6, atol=1e-6)
    assert not np.allclose(part[..., :16], x[..., :16])
    # the reference's independent rotary agrees at both fractions
    for frac in (0.25, 1.0):
        np.testing.assert_allclose(L.apply_rope(x, pos, 1e4, frac),
                                   R.rope(x, 1e4, frac), rtol=1e-5,
                                   atol=1e-5)


def test_param_counts_are_the_published_ones():
    stablelm, qwen = configs.get("stablelm-1.6b"), configs.get("qwen3-4b")
    assert stablelm.param_count() == 1_644_515_328
    assert qwen.param_count() == 4_022_468_096
    for cfg in (stablelm, qwen):
        leaves = jax.tree_util.tree_leaves(params_shapes(cfg))
        assert sum(a.size for a in leaves) == cfg.param_count()


def _leaf_names(cfg):
    paths = jax.tree_util.tree_flatten_with_path(params_shapes(cfg))[0]
    return {str(p[-1].key) for p, _ in paths}


def test_qwen3_4b_has_no_new_leaves():
    assert _leaf_names(configs.get("qwen3-4b")) == {
        "emb", "ln_f", "ln1", "ln2", "wq", "wk", "wv", "wo", "q_norm",
        "k_norm", "w1", "w3", "w2"}


def test_stablelm_is_the_published_architecture():
    cfg = configs.get("stablelm-1.6b")
    assert (cfg.layernorm, cfg.rotary_fraction, cfg.qkv_bias) == (
        True, 0.25, True)
    assert cfg.head_dim == 64 and not cfg.tie_embeddings
    assert cfg.source == URL
    assert _leaf_names(cfg) == {
        "emb", "unemb", "ln_f", "ln_f_b", "ln1", "ln1_b", "ln2", "ln2_b",
        "wq", "wk", "wv", "wo", "bq", "bk", "bv", "w1", "w3", "w2"}
    conf = json.loads(CONFIG.read_text())
    assert conf["source"] == URL and conf["reduced"] == []
    assert conf["published"]["partial_rotary_factor"] == 0.25
    assert conf["published"]["use_qkv_bias"] is True
    for k, v in conf["arch_config"].items():
        assert getattr(cfg, "head_dim" if k == "d_head" else k) == v, k
