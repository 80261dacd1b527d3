"""Benchmark-runner laws: the process-parallel sweep runner is
deterministic (``--jobs 1`` == ``--jobs N``) and the perf bench produces
a well-formed trajectory artifact."""
import json
import sys
from pathlib import Path

import pytest

REPO_ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO_ROOT))   # import the benchmarks package


def test_parallel_runner_is_deterministic(capsys):
    """Suite output (tables + CSV rows) is identical for 1 and 2 workers —
    including the open-loop serving curve + saturation suite."""
    from benchmarks.run import run_suites

    rows1, failed1 = run_suites(["mix", "serving", "gc_policies"],
                                smoke=True, jobs=1)
    out1 = capsys.readouterr().out
    rows2, failed2 = run_suites(["mix", "serving", "gc_policies"],
                                smoke=True, jobs=2)
    out2 = capsys.readouterr().out
    assert failed1 == failed2 == []
    assert rows1 == rows2
    assert out1 == out2
    assert any(r.startswith("mix/") for r in rows1)
    assert any(r.startswith("serving/") and "/saturation," in r
               for r in rows1)
    assert any(r.startswith("gcpolicy/wa/") for r in rows1)
    assert any(r.startswith("gcpolicy/saturation/") for r in rows1)


def test_runner_reports_unknown_suite():
    from benchmarks.run import run_suites

    rows, failed = run_suites(["nope"], jobs=1)
    assert failed == ["nope"]
    assert any(r.startswith("error/nope") for r in rows)


def test_perf_bench_writes_trajectory_artifact(tmp_path):
    from benchmarks import perf_bench

    path = tmp_path / "BENCH_sim_perf.json"
    rows = perf_bench.run_perf(smoke=True, repeats=1,
                               json_path=str(path), check=False)
    data = json.loads(path.read_text())
    assert data["schema"] == "sim-perf-trajectory/v1"
    assert data["current"]["mix_events_per_sec"] > 0
    assert data["current"]["gc_events_per_sec"] > 0
    assert data["current"]["serving_events_per_sec"] > 0
    assert any(r.startswith("simperf/mix/") for r in rows)
    assert any(r.startswith("simperf/serving/") for r in rows)


def test_committed_perf_artifact_records_speedup():
    """The committed BENCH_sim_perf.json is the perf-trajectory artifact:
    baseline (pre fast-path engine) + current + >=3x speedup on mix+gc."""
    data = json.loads((REPO_ROOT / "BENCH_sim_perf.json").read_text())
    assert data["schema"] == "sim-perf-trajectory/v1"
    if data.get("harness", {}).get("smoke"):
        pytest.skip("artifact was locally rewritten by a --smoke probe; "
                    "the committed version is a full run")
    for key in ("mix_events_per_sec", "gc_events_per_sec"):
        assert data["baseline"][key] > 0
        assert data["current"][key] > 0
        assert data["speedup"][key] >= 3.0
    # the serving suite (PR 4) is tracked from its introduction: current
    # only — it has no pre-fast-path baseline to speed up against
    assert data["current"]["serving_events_per_sec"] > 0


def test_pool_workers_run_jax_on_the_cpu(monkeypatch):
    """A worker inherits the parent's environment, then pins its JAX to
    the CPU before any suite imports JAX: the accelerator stays free."""
    import multiprocessing
    import os
    from concurrent.futures import ProcessPoolExecutor

    from benchmarks.run import _host_only_worker

    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    ctx = multiprocessing.get_context("spawn")
    with ProcessPoolExecutor(1, mp_context=ctx,
                             initializer=_host_only_worker) as pool:
        assert pool.submit(os.getenv, "JAX_PLATFORMS").result() == "cpu"


def test_device_suites_stay_in_the_parent(monkeypatch):
    """With ``--jobs > 1`` the suites in DEVICE_SUITES run in the parent
    process (which holds the device); the rest go to workers.  The probe
    suite exists only in the parent's table, so a worker would fail it."""
    import os

    from benchmarks import run

    ran_in = []

    def probe():
        ran_in.append(os.getpid())
        return ["probe/ok,1,"]

    table = run._suite_table()
    monkeypatch.setattr(run, "_suite_table", lambda: dict(table, probe=probe))
    monkeypatch.setattr(run, "DEVICE_SUITES", {"probe"})
    rows, failed = run.run_suites(["latmodel", "probe"], jobs=2)
    assert failed == []
    assert ran_in == [os.getpid()]
    assert "probe/ok,1," in rows
    assert any(r.startswith("latmodel/") for r in rows)
