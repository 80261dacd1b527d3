"""Device time of a cell's step programs split by the model's named scopes.

  python3 chipbench/scopes.py --workload qwen3-4b.decode --seed 7 \
      --seconds 50

Runs one traced window of the cell, as ``run.py --trace 1`` does, and maps
every op of each step program to its bucket (``repro.models.scopes``: the
model's named scopes, read from the optimized HLO of the very programs the
cell's ``Server`` compiled, afresh). Prints one JSON line: the run's
``correct``, its end-to-end metrics (timed with the profiler on), the
per-layer metrics of the cell, and for each program its device time and
op self time per call, that self time split by bucket, and the costliest
ops of each bucket, in ms. Not part of a benchmark run.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import bisect  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from collections import defaultdict  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, Optional  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402

from chipbench import harness as H  # noqa: E402
from chipbench import run as RUN  # noqa: E402
from chipbench import trace as T  # noqa: E402
from repro.models import scopes as SC  # noqa: E402

TOP = 10


def program_op_times(tr: T.Trace, window: Optional[T.Interval] = None
                     ) -> Dict[str, Dict[str, float]]:
    """{program: {op: self seconds}} over the executions of each program on
    the first device that lie inside the window (those ``program_calls``
    counts), taking the ops that start inside an execution."""
    lo, hi = T.window_of(tr) if window is None else window
    dev = tr.devices[0]
    ops = sorted(dev.ops, key=lambda o: o[1])
    starts = [s for _, s, _ in ops]
    inside = defaultdict(list)
    for m, s, e in dev.modules:
        if s >= lo and e <= hi:
            inside[m] += ops[bisect.bisect_left(starts, s):
                             bisect.bisect_left(starts, e)]
    return {m: dict(T.self_times(o)) for m, o in inside.items()}


def bucket_ms(op_s: Dict[str, float], op_map: Dict[str, str], calls: int
              ) -> Dict[str, float]:
    """Self time per call of each bucket, in ms; ops the map lacks are
    ``unscoped``."""
    out = dict.fromkeys(SC.BUCKETS, 0.0)
    for op, s in op_s.items():
        out[op_map.get(op, "unscoped")] += s / calls * 1e3
    return out


def _module(hlo_text: str) -> str:
    return re.match(r"HloModule ([\w.\-]+)", hlo_text).group(1)


def record_programs(driver, texts: Dict[str, str]) -> None:
    """Keep the optimized HLO of each step program the cell's ``Server``
    compiles, by module name (the trace's program name)."""
    init = driver.Server.__init__

    def record(self, *a, **k):
        init(self, *a, **k)
        for fn in (self.prefill_fn, self.serve_fn):
            text = fn.as_text()
            texts[_module(text)] = text
    driver.Server.__init__ = record


def load_trace(trace_dir: str, chips: int) -> T.Trace:
    files = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}, "
                           f"found {len(files)}")
    tr = T.load(files[0])
    tr.devices = tr.devices[:chips]
    return tr


def measure(argv, root: Path = ROOT, require_chips=H.require_chips) -> dict:
    args = RUN.parse(argv)
    cell, devices, peaks, driver = H.open_cell(root, args.workload,
                                               require_chips)
    # the persistent cache's key leaves out metadata: it would hand back a
    # program compiled by a tree without the scopes, all of it unscoped
    jax.config.update("jax_enable_compilation_cache", False)
    texts: Dict[str, str] = {}
    record_programs(driver, texts)
    tmp = tempfile.mkdtemp(prefix="chipbench-")
    try:
        ctx = H.Context(cell=cell, seed=args.seed, seconds=args.seconds,
                        trace=True, devices=devices, peaks=peaks,
                        t_start=T_START, tmp=tmp)
        outcome = driver.run(ctx)
        tr = load_trace(outcome.trace_dir, len(devices))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    summary = T.reduce(tr)
    programs = {}
    for prog, op_s in program_op_times(tr).items():
        if prog not in texts:
            continue
        op_map = SC.op_scopes(texts[prog])
        calls = summary.program_calls[prog]
        top = defaultdict(list)
        for op, s in sorted(op_s.items(), key=lambda kv: -kv[1]):
            ops = top[op_map.get(op, "unscoped")]
            if len(ops) < TOP:
                ops.append([op, s / calls * 1e3])
        programs[prog] = {
            "calls": calls,
            "program_ms": summary.program_s[prog] / calls * 1e3,
            "self_ms": sum(op_s.values()) / calls * 1e3,
            "buckets_ms": bucket_ms(op_s, op_map, calls),
            "top_ops_ms": dict(top)}
    return {"correct": (all(c.ok for c in outcome.checks)
                        and bool(outcome.checks)),
            "checks": {c.name: {"value": c.value, "limit": c.limit}
                       for c in outcome.checks},
            "end_to_end": outcome.end_to_end,
            "per_layer": RUN.per_layer(cell, outcome, summary, len(devices),
                                       peaks),
            "busy_s": summary.busy_s, "window_s": summary.window_s,
            "programs": programs}


def main() -> int:
    try:
        out = measure(sys.argv[1:])
    except H.NoResult as e:
        print(f"chipbench: {e}; no result", file=sys.stderr, flush=True)
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
