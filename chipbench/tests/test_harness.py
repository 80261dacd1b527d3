"""The harness end to end on the CPU at a tiny size, and its refusals."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from chipbench import run as RUN
from chipbench import trace as T
from chipbench.tests import _tiny

FIXTURE = Path(__file__).parent / "data" / "small.xplane.pb"


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return _tiny.make_checkout(tmp_path_factory.mktemp("bench"))


def args(trace=0, seed=2**31 + 11):
    return ["--workload", "tiny-gqa.tiny", "--seed", str(seed),
            "--seconds", "1.5", "--trace", str(trace)]


def test_new_cell_runs_from_new_files(checkout):
    res = json.loads(RUN.run(args(), root=checkout,
                             require_chips=_tiny.cpu_chips))
    assert res["correct"] is True
    assert set(res["metrics"]) == {"tokens_per_s", "itl_ms_p95", "setup_s"}
    assert res["metrics"]["tokens_per_s"]["unit"] == "tokens/s"
    assert res["attempted"] >= 2 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert res["device"]["count"] == 1


def test_new_per_layer_metric_from_its_reader(checkout, monkeypatch):
    # the CPU has no device planes to trace: read the recorded v5e trace
    monkeypatch.setattr(T, "reduce_dir",
                        lambda d, chips: T.reduce(T.load(str(FIXTURE))))
    res = json.loads(RUN.run(args(trace=1), root=checkout,
                             require_chips=_tiny.cpu_chips))
    # the cell's per-layer metric, and no other cell's
    assert set(res["metrics"]) == {"tokens_served"}
    assert res["metrics"]["tokens_served"]["value"] > 0
    assert res["device"]["busy_s"] > 0 and res["device"]["window_s"] > 0
    assert len(res["breakdown"]["device_ops"]) <= 10


def _cli(cwd, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(extra_env or {}))
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "qwen3-4b.decode",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_without_a_tpu():
    p = _cli(_tiny.REPO)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_refuses_an_unknown_device_kind(checkout):
    class Dev:
        device_kind, platform = "TPU v9 imaginary", "tpu"
    with pytest.raises(RUN.H.NoResult, match="not in peaks.json"):
        RUN.run(args(), root=checkout, require_chips=lambda n: [Dev()] * n)


def test_refuses_with_only_the_benchmark_files(tmp_path):
    import shutil
    shutil.copytree(_tiny.REPO / "chipbench", tmp_path / "chipbench")
    shutil.copy(_tiny.REPO / "BENCHMARK.json", tmp_path)
    p = _cli(tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_a_batch_past_the_close_is_served_to_its_end_untimed():
    import jax
    import numpy as np
    from chipbench import harness as H
    from chipbench.drivers import serve_closed_loop as S
    cfg = H.arch_config({"arch": "qwen3-4b", "arch_config": _tiny.TINY_SHAPE})
    params = S.M.init_params(cfg, jax.random.PRNGKey(0))
    server = S.Server(cfg, params, 2, 8, 5)
    prompts = np.zeros((2, 8), np.int32)
    toks, times = server.run_batch(prompts, close=0.0, finish=True)
    assert toks.shape == (2, 5) and times.size == 0
    toks, times = server.run_batch(prompts, close=0.0)
    assert toks.shape == (2, 0) and times.size == 0
