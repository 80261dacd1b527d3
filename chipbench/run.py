"""Run one benchmark cell on the chip and print its result line.

  python3 chipbench/run.py --workload qwen3-4b.decode --seed 7 \
      --seconds 50 --trace 0

With ``--trace 0`` the metrics are the cell's end-to-end metrics, timed on
the host; with ``--trace 1`` a profiler trace of the window gives its
per-layer metrics. Every run checks the window's output against the
plain float32 reference (``chipbench/reference``) and prints each number
compared beside its limit, last on stderr and last in the result line.

Exits non-zero with no result line when JAX finds no TPU, fewer chips than
the cell asks for, or a device kind missing from ``chipbench/peaks.json``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness as H  # noqa: E402
from chipbench import trace as T  # noqa: E402


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def per_layer(cell: H.Cell, outcome: H.Outcome, summary, chips: int,
              peaks: dict) -> dict:
    """Each per-layer metric from its own reader; a reader that finds
    nothing to read returns None and the metric is left out."""
    readings = T.Readings(trace=summary, host=outcome.host,
                          shape=cell.config["arch_config"], peaks=peaks,
                          chips=chips)
    out = {}
    for m in cell.per_layer:
        reader = H.load_module(cell.base / "metrics" / f"{m['name']}.py",
                               "metric")
        value = reader.read(readings)
        if value is not None:
            out[m["name"]] = value
    return out


def run(argv, root: Path = ROOT, require_chips=H.require_chips) -> str:
    """One run; returns the result line. Raises NoResult before any
    result exists."""
    args = parse(argv)
    cell, devices, peaks, driver = H.open_cell(root, args.workload,
                                               require_chips)
    dev = devices[0]
    tmp = tempfile.mkdtemp(prefix="chipbench-")
    try:
        ctx = H.Context(cell=cell, seed=args.seed, seconds=args.seconds,
                        trace=bool(args.trace), devices=devices, peaks=peaks,
                        t_start=T_START, tmp=tmp)
        outcome = driver.run(ctx)
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(devices),
                  "memory_peak_bytes": outcome.host["memory_peak_bytes"]}
        breakdown = None
        if args.trace:
            t_read = time.perf_counter()
            summary = T.reduce_dir(outcome.trace_dir, len(devices))
            print(f"trace read in {time.perf_counter() - t_read:.3f} s",
                  file=sys.stderr, flush=True)
            metrics = per_layer(cell, outcome, summary, len(devices), peaks)
            device.update(busy_s=summary.busy_s, window_s=summary.window_s)
            breakdown = {"device_ops": summary.device_ops,
                         "idle_gaps": summary.idle_gaps}
        else:
            metrics = {m["name"]: outcome.end_to_end[m["name"]]
                       for m in cell.end_to_end}
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    for c in outcome.checks:
        print(f"check {c.name}: {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr, flush=True)
    return H.result_line(cell, outcome, metrics, device, breakdown)


def main() -> int:
    try:
        line = run(sys.argv[1:])
    except H.NoResult as e:
        print(f"chipbench: {e}; no result", file=sys.stderr, flush=True)
        return 2
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
