"""Readings that the limits of a cell's check are set from: for each seed,
one run of the cell (its window at the cell's own load and size) with the
program's numbers and the control's, the reference computed in the
precision below the configuration's (``reference/dense_gqa.fp8_mm``).

  python3 chipbench/control.py --workload qwen3-4b.decode --seconds 2 \
      --seeds 11 12 13

Prints one JSON line per seed: the program's ``correct`` and ``checks`` and
the control's, each as the harness's result line gives them. Not part of a
benchmark run.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness as H  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    a = ap.parse_args()
    try:
        cell, devices, peaks, driver = H.open_cell(ROOT, a.workload)
    except H.NoResult as e:
        print(f"chipbench: {e}; no result", file=sys.stderr)
        return 2
    for seed in a.seeds:
        tmp = tempfile.mkdtemp(prefix="chipbench-")
        try:
            ctx = H.Context(cell=cell, seed=seed, seconds=a.seconds,
                            trace=False, devices=devices, peaks=peaks,
                            t_start=time.perf_counter(), tmp=tmp,
                            control=True)
            out = driver.run(ctx)
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
        res = {"seed": seed}
        for side, checks in (("program", out.checks),
                             ("control", out.control_checks)):
            line = json.loads(H.result_line(
                cell, dataclasses.replace(out, checks=checks), {}, {}, None))
            res[side] = {k: line[k] for k in ("correct", "checks")}
        res.update(out.host["check"], end_to_end=out.end_to_end)
        print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
