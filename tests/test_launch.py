"""Launch-layer laws on the CPU: the serving driver's accounting, jitted
init, training on a mesh, the compile-cache location, sharding hints and
cache specs, and chip_smoke.py's cache-consistency check at a reduced
config."""
import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.launch import cache as C
from repro.launch import sharding as SH
from repro.launch.mesh import make_mesh
from repro.launch.serve import serve
from repro.launch.specs import cache_shapes
from repro.launch.train import train
from repro.models import layers as L
from repro.models import model as M

REPO_ROOT = Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def chip_smoke():
    sys.path.insert(0, str(REPO_ROOT))
    import chip_smoke as mod
    return mod


def test_serve_compiles_outside_the_window_and_pads_the_last_batch():
    """3 requests at batch 2: the last batch runs with one idle slot on
    the already-compiled shape; every request gets max_new tokens."""
    res = serve("qwen3-4b", n_requests=3, batch=2, prompt_len=8, max_new=3)
    assert res["requests"] == 3
    assert res["tokens"] == 9
    assert res["compile_s"] > 0
    assert res["param_bytes"] == 2 * res["params"]          # bf16
    assert 0 < res["latency_ms_p50"] <= res["latency_ms_p99"]


def test_jitted_init_matches_eager_init():
    """Jitting the initializer keeps every weight bit-identical to the
    op-by-op draw it replaced."""
    cfg = configs.get("qwen3-4b").reduced()
    key = jax.random.PRNGKey(5)
    jitted = jax.tree_util.tree_leaves(M.init_params(cfg, key))
    eager = jax.tree_util.tree_leaves(M.init_params.__wrapped__(cfg, key))
    assert len(jitted) == len(eager)
    for a, b in zip(jitted, eager):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_train_on_a_mesh_matches_the_default_device():
    mesh = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    plain = train("tinyllama-1.1b", steps=1, batch=2, seq=16)
    meshed = train("tinyllama-1.1b", steps=1, batch=2, seq=16, mesh=mesh)
    np.testing.assert_allclose(meshed["losses"], plain["losses"], rtol=1e-5)
    np.testing.assert_allclose(meshed["grad_norms"], plain["grad_norms"],
                               rtol=1e-5)
    for leaf in jax.tree_util.tree_leaves(meshed["state"]):
        assert isinstance(leaf.sharding, NamedSharding)
        assert leaf.sharding.mesh.axis_names == ("data", "model")
    # the model's sharding hints are unbound again afterwards
    assert L.data_axes() == () and L.model_axis() is None


def test_compile_cache_defaults_to_the_repo(monkeypatch):
    prev = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        assert C.use_compile_cache() == str(REPO_ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(
            REPO_ROOT / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_compile_cache_env_var_wins(monkeypatch, tmp_path):
    prev = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert C.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == prev     # untouched


def test_sharding_hints_apply_only_under_a_mesh():
    x = jnp.ones((4, 8))
    assert L._maybe_shard(x, P("nowhere")) is x             # no active mesh
    mesh = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    with jax.set_mesh(mesh), pytest.raises(Exception, match="nowhere"):
        jax.jit(lambda y: L._maybe_shard(y, P("nowhere")))(x)


@pytest.mark.parametrize("batch", [1, 3, 8])
def test_cache_specs_follow_the_kv_order(batch):
    """Every K/V cache order (``L.kv_order`` with 128-wide heads: [B, Hkv,
    S, dh] at 1, [B, S, Hkv, dh] at 3, [S, Hkv, B, dh] at 8): the data axis
    on the batch dim, the model axis on the sequence dim, nothing else."""
    cfg = dataclasses.replace(configs.get("tinyllama-1.1b").reduced(),
                              d_head=128)
    mesh = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    shapes = cache_shapes(cfg, batch, 16)
    specs = SH.cache_specs(cfg, shapes, mesh, batch)
    order = L.kv_order(cfg, batch)
    assert order == {1: "bhsd", 3: "bshd", 8: "shbd"}[batch]
    want = [None] * 5
    want[1 + order.index("b")] = ("data",)
    want[1 + order.index("s")] = "model"
    for name in ("k", "v"):
        assert shapes[0][name].shape[1 + order.index("b")] == batch
        assert specs[0][name] == P(*want)


def test_cache_consistency_check_passes_and_catches_a_bad_index(
        chip_smoke, monkeypatch):
    """chip_smoke's cached-vs-cache-free comparison at a reduced qwen3-4b:
    within tolerance as the model stands, far outside it when decode
    writes and reads the cache one slot late."""
    cfg = dataclasses.replace(configs.get("qwen3-4b").reduced(), n_layers=4,
                              block_pattern=())
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    prompts = np.random.default_rng(0).integers(0, cfg.vocab, (2, 24),
                                                dtype=np.int32)
    err = chip_smoke.cache_consistency(cfg, params, prompts, 4)
    assert err <= chip_smoke.CACHE_REL_TOL
    real = M.decode_step
    monkeypatch.setattr(M, "decode_step", lambda cfg, p, t, i, c: real(
        cfg, p, t, i + 1, c))
    bad = chip_smoke.cache_consistency(cfg, params, prompts, 4)
    assert bad > chip_smoke.CACHE_REL_TOL


def test_chip_smoke_refuses_the_cpu(chip_smoke):
    with pytest.raises(SystemExit, match="needs a TPU"):
        chip_smoke.require_tpu(1)
