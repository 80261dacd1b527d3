"""Substrate tests: optimizer, schedules, data, checkpoints, elasticity."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager, load_checkpoint, save_checkpoint
from repro.data import SyntheticLM
from repro.launch.elastic import (SimulatedFailure, StragglerMonitor,
                                  run_elastic)
from repro.optim import adamw_init, adamw_update, make_schedule, wsd_schedule


def test_adamw_optimizes_quadratic():
    params = {"w": jnp.asarray([5.0, -3.0])}
    state = adamw_init(params)
    for _ in range(200):
        grads = jax.grad(lambda p: jnp.sum(p["w"] ** 2))(params)
        params, state, m = adamw_update(params, grads, state, lr=0.05,
                                        weight_decay=0.0)
    assert float(jnp.abs(params["w"]).max()) < 0.3
    assert int(state.step) == 200


def test_grad_clipping_bounds_update():
    params = {"w": jnp.zeros((4,))}
    state = adamw_init(params)
    grads = {"w": jnp.full((4,), 1e9)}
    new_params, state, m = adamw_update(params, grads, state, lr=0.1,
                                        clip_norm=1.0, weight_decay=0.0)
    assert float(m["grad_norm"]) > 1e8
    assert float(jnp.abs(new_params["w"]).max()) < 1.0


def test_wsd_schedule_phases():
    lr = lambda s: float(wsd_schedule(s, 1.0, warmup=10, stable=80, decay=10))
    assert lr(0) == pytest.approx(0.1)   # warmup starts at (step+1)/warmup
    assert lr(4) == pytest.approx(0.5)
    assert lr(50) == pytest.approx(1.0)
    assert lr(95) < 1.0
    assert lr(100) == pytest.approx(0.1)
    cos = make_schedule("cosine", 1.0, 100)
    assert float(cos(100)) == pytest.approx(0.1, abs=0.02)


def test_data_determinism_and_sharding():
    pipe = SyntheticLM(vocab=128, seq_len=16, global_batch=8, seed=7)
    b1, b2 = pipe.batch(3), pipe.batch(3)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    assert not np.array_equal(pipe.batch(4)["tokens"], b1["tokens"])
    # shards partition the global batch
    parts = [pipe.shard_for(3, i, 4)["tokens"] for i in range(4)]
    np.testing.assert_array_equal(np.concatenate(parts), b1["tokens"])
    # labels are next-token shifted
    np.testing.assert_array_equal(b1["tokens"][:, 1:], b1["labels"][:, :-1])


def test_checkpoint_roundtrip_and_validation(tmp_path):
    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": {"c": jnp.ones((4,), jnp.bfloat16),
                  "s": jnp.asarray(3, jnp.int32)}}
    d = str(tmp_path)
    save_checkpoint(d, 7, tree, extra={"note": "x"})
    restored, manifest = load_checkpoint(d, tree)
    assert manifest["step"] == 7
    for l1, l2 in zip(jax.tree_util.tree_leaves(tree),
                      jax.tree_util.tree_leaves(restored)):
        np.testing.assert_array_equal(np.asarray(l1, np.float32),
                                      np.asarray(l2, np.float32))
    # corruption detection
    import numpy as _np
    npz = os.path.join(d, "step_000000007", "arrays.npz")
    data = dict(_np.load(npz, allow_pickle=False))
    data["leaf_0"] = data["leaf_0"] + 1
    _np.savez(npz, **data)
    with pytest.raises(IOError):
        load_checkpoint(d, tree)


def test_checkpoint_manager_async_retention(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    tree = {"w": jnp.zeros((3,))}
    for s in (1, 2, 3, 4):
        mgr.save(s, tree)
    mgr.wait()
    steps = sorted(x for x in os.listdir(tmp_path) if x.startswith("step_"))
    assert len(steps) == 2
    assert mgr.latest_step() == 4


def test_straggler_monitor():
    mon = StragglerMonitor(threshold=2.0, min_samples=3)
    for _ in range(5):
        assert not mon.observe(1.0)
    assert mon.observe(5.0)
    assert mon.flagged == 1
    assert not mon.observe(1.1)
    assert mon.rescale_factor(16, 1) == pytest.approx(16 / 15)


def test_run_elastic_restarts():
    calls = {"n": 0}

    def fn(resume):
        calls["n"] += 1
        if calls["n"] < 3:
            raise SimulatedFailure("boom")
        return 42

    assert run_elastic(fn, max_restarts=5) == 42
    assert calls["n"] == 3
    calls["n"] = 0
    with pytest.raises(SimulatedFailure):
        run_elastic(fn, max_restarts=1)
