"""Benchmark entry point: one function per paper table/figure plus the
pressure, fault-replay, kernel and simulator-perf benches.

Prints human-readable tables followed by a machine-readable
``name,value,derived`` CSV block.

  PYTHONPATH=src python -m benchmarks.run            # everything
  PYTHONPATH=src python -m benchmarks.run --only fig7a,table3
  PYTHONPATH=src python -m benchmarks.run --only mix,gc --jobs 4
  PYTHONPATH=src python -m benchmarks.run --only gc --profile

Parallelism: ``--jobs N`` farms the selected suites across N worker
processes.  Every simulation suite is internally seeded (hashed
pseudo-random streams, no global RNG), so the workers share nothing and
the output — both the per-suite tables and the CSV block — is printed in
the deterministic ``--only`` order regardless of completion order:
``--jobs 1`` and ``--jobs N`` produce identical suite output for every
deterministic suite.  (The wall-clock-measuring suite ``simperf`` prints
timings, which naturally vary run to run and are skewed when siblings
saturate the CPU; run it with ``--jobs 1`` when the numbers matter.)
Workers run JAX on the CPU only: an accelerator belongs to one process,
so the suites that run on the device (``DEVICE_SUITES``) stay in the
parent.

Profiling: ``--profile`` wraps the selected suites in cProfile and prints
the top-20 cumulative entries afterwards, so perf work starts from data.
It forces sequential execution (a profile of worker stubs is useless).
``--profile-out PATH`` (implies ``--profile``) additionally dumps the
full pstats file for offline digging (snakeviz, ``pstats.Stats(PATH)``).
"""
from __future__ import annotations

import argparse
import contextlib
import functools
import io
import json
import os
import sys
import time
from typing import Dict, List, Optional, Tuple

#: suites whose signature takes a ``smoke`` kwarg (CI-sized shrink)
SMOKE_AWARE = {"mix", "gc", "gc_policies", "serving", "faults", "fleet"}
#: suites that run on the default JAX device, which a worker must not take
DEVICE_SUITES = {"kernels"}


def _suite_table() -> Dict:
    from benchmarks import (faults_bench, fleet_bench, kernel_bench,
                            paper_figures, perf_bench, pressure_bench,
                            serving_bench)

    return {
        "table3": paper_figures.table3_characterize,
        "fig7a": paper_figures.fig5_fig7a_speedup,
        "fig7b": paper_figures.fig7b_energy,
        "fig8": paper_figures.fig8_tail_latency,
        "fig9": paper_figures.fig9_decisions,
        "fig10": paper_figures.fig10_timeline,
        "overhead": paper_figures.overhead_analysis,
        "kernels": kernel_bench.kernel_microbench,
        "latmodel": kernel_bench.resource_latency_table,
        "pressure": pressure_bench.pressure_sweep,
        "fault": pressure_bench.fault_replay,
        "mix": pressure_bench.tenant_interference,
        "gc": pressure_bench.gc_interference,
        "gc_policies": pressure_bench.gc_policies,
        "serving": serving_bench.serving_curve,
        "faults": faults_bench.fault_injection,
        "fleet": fleet_bench.fleet_serving,
        "simperf": perf_bench.perf_suite,
    }


def _run_one(name: str, smoke: bool) -> Tuple[str, List[str], str, Optional[str]]:
    """Run one suite with captured stdout.

    Top-level so it pickles for worker processes; returns
    ``(name, csv_rows, captured_output, error)``."""
    fn = _suite_table().get(name)
    if fn is None:
        return name, [f"error/{name},unknown suite,"], "", f"unknown suite {name}"
    if smoke and name in SMOKE_AWARE:
        fn = functools.partial(fn, smoke=True)
    buf = io.StringIO()
    try:
        with contextlib.redirect_stdout(buf):
            rows = fn()
        return name, rows, buf.getvalue(), None
    except Exception as e:  # pragma: no cover - exercised via failed suites
        return name, [f"error/{name},{e},"], buf.getvalue(), str(e)


def _host_only_worker() -> None:
    """Pool initializer: the worker's JAX (imported later, by the suites)
    stays on the CPU and leaves any accelerator to the parent."""
    os.environ["JAX_PLATFORMS"] = "cpu"


def run_suites(wanted: List[str], smoke: bool = False, jobs: int = 1,
               profile: bool = False,
               profile_out: Optional[str] = None) -> Tuple[List[str], List[str]]:
    """Run ``wanted`` suites; returns ``(csv_rows, failed_names)``.

    Output (tables + CSV rows) is assembled in ``wanted`` order for any
    ``jobs`` value, so N=1 and N>1 runs are byte-identical."""
    wanted = [w.strip() for w in wanted]
    csv_rows = ["name,value,derived"]
    failed: List[str] = []

    profiler = None
    if profile_out is not None:
        profile = True
    if profile:
        import cProfile
        jobs = 1
        profiler = cProfile.Profile()
        profiler.enable()

    if jobs <= 1:
        results = [_run_one(name, smoke) for name in wanted]
    else:
        import multiprocessing
        from concurrent.futures import ProcessPoolExecutor
        # spawn, not fork: jax (imported by the workload suites) runs
        # background threads, and forking a threaded process can deadlock
        ctx = multiprocessing.get_context("spawn")
        with ProcessPoolExecutor(max_workers=jobs, mp_context=ctx,
                                 initializer=_host_only_worker) as pool:
            futures = {name: pool.submit(_run_one, name, smoke)
                       for name in wanted if name not in DEVICE_SUITES}
            local = {name: _run_one(name, smoke)
                     for name in wanted if name in DEVICE_SUITES}
            results = [local[name] if name in local
                       else futures[name].result()
                       for name in wanted]            # wanted order

    if profiler is not None:
        profiler.disable()

    for name, rows, output, error in results:
        if output:
            print(output, end="")
        if error is not None:
            print(f"[benchmarks] suite {name} failed: {error}",
                  file=sys.stderr)
            failed.append(name)
        csv_rows.extend(rows)

    if profiler is not None:
        import pstats
        if profile_out is not None:
            profiler.dump_stats(profile_out)
            print(f"[benchmarks] full profile written to {profile_out}")
        print("\n===== cProfile (top 20 cumulative) =====")
        pstats.Stats(profiler).sort_stats("cumulative").print_stats(20)
    return csv_rows, failed


def write_run_report(path: str, csv_rows: List[str],
                     failed: List[str], smoke: bool) -> None:
    """Structured run report: the CSV metrics plus a tail-latency blame
    summary from one telemetry-on serving run, stamped with the git SHA
    and hardware-spec hash so reports join across commits and refuse
    joins across spec changes (``repro.sim.analysis diff``)."""
    import hashlib

    from repro.hw.ssd_spec import DEFAULT_SSD
    from repro.sim import (CatalogEntry, FTLConfig, HostIOStream,
                           PoissonArrivals, ServingConfig, SessionCatalog,
                           TelemetryConfig, simulate_serving)
    from repro.sim.analysis import _git_sha, build_report
    from repro.workloads import get_trace

    # one small serving-under-GC run with the recorder on: post-hoc
    # analysis only, so the benchmark numbers above are never perturbed
    catalog = SessionCatalog(
        [CatalogEntry("jacobi1d", get_trace("jacobi1d", "tiny"))], seed=7)
    ftl = FTLConfig(blocks_per_die=4, pages_per_block=8, op_ratio=0.28,
                    prefill=0.9, gc_reserve_blocks=1)
    res = simulate_serving(
        catalog,
        PoissonArrivals(rate_per_sec=4000,
                        n_sessions=12 if smoke else 32, seed=11),
        "conduit",
        serving=ServingConfig(keep_session_results=False,
                              little_law_warn_tol=float("inf")),
        io_stream=HostIOStream(rate_iops=40_000, read_fraction=0.7,
                               n_requests=64 if smoke else 256,
                               n_logical_pages=ftl.logical_pages()),
        ftl=ftl,
        telemetry=TelemetryConfig(spans=True, audit=True,
                                  interval_ns=20_000.0))
    metrics = {}
    for row in csv_rows[1:]:
        parts = row.split(",")
        if len(parts) >= 2:
            metrics[parts[0]] = {"value": parts[1],
                                 "derived": ",".join(parts[2:])}
    report = {
        "schema": "conduit-bench-report/v1",
        "git_sha": _git_sha(),
        "spec_sha": hashlib.sha256(
            repr(DEFAULT_SSD).encode()).hexdigest()[:16],
        "smoke": smoke,
        "failed_suites": failed,
        "metrics": metrics,
        "analysis": res.analysis(),
    }
    with open(path, "w") as f:
        json.dump(report, f, indent=1, sort_keys=True)
    print(f"[benchmarks] run report written to {path}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list: table3,fig7a,fig7b,fig8,fig9,fig10,"
                         "overhead,kernels,latmodel,pressure,fault,mix,gc,"
                         "gc_policies,serving,faults,fleet,simperf")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized configurations for smoke-aware suites "
                         "(mix, gc, gc_policies, serving): tiny sweeps "
                         "that only check the entry points still run")
    ap.add_argument("--jobs", type=int, default=1,
                    help="worker processes for independent suites (output "
                         "is identical for any N on deterministic suites; "
                         "timing suites like simperf belong on --jobs 1)")
    ap.add_argument("--profile", action="store_true",
                    help="wrap the selected suites in cProfile and print "
                         "the top-20 cumulative entries (forces --jobs 1)")
    ap.add_argument("--profile-out", default=None, metavar="PATH",
                    help="write the full pstats dump to PATH for offline "
                         "analysis (implies --profile)")
    ap.add_argument("--report", default=None, metavar="PATH",
                    help="write a structured JSON run report: the CSV "
                         "metrics plus a tail-latency blame summary, git "
                         "SHA and spec hash (conduit-bench-report/v1)")
    args = ap.parse_args()

    wanted = (args.only.split(",") if args.only else list(_suite_table()))
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    t0 = time.time()
    csv_rows, failed = run_suites(wanted, smoke=args.smoke, jobs=args.jobs,
                                  profile=args.profile,
                                  profile_out=args.profile_out)
    print(f"\n[benchmarks] completed in {time.time()-t0:.0f}s")
    print("\n===== CSV =====")
    for row in csv_rows:
        print(row)
    if args.report is not None:
        write_run_report(args.report, csv_rows, failed, args.smoke)
    if failed:  # nonzero exit so the CI bench-smoke step actually gates
        sys.exit(f"[benchmarks] failing suites: {', '.join(failed)}")


if __name__ == "__main__":
    main()
