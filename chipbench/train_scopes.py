"""A training cell's traced run, with its step's device time split by the
model's named scopes.

  python3 chipbench/train_scopes.py --workload stablelm-1.6b.train-2x2 \
      --seed 7 --seconds 50

Runs ``run.py ... --trace 1`` as the benchmark does, with the fp8 control
of the check computed after the window as ``control.py`` does, and prints
the result line. Then one more JSON line: the check's readings, the
program's and the control's (``control.py``'s), and for the train step
program its device time and op self time per call on the first chip, that
self time split by bucket (``repro.models.scopes``; ``optimizer`` is the
AdamW update) and the costliest ops of each bucket, in ms. The buckets are
read from the optimized HLO of the very step the cell ran, compiled once
more after the run with the persistent compile cache off (its key leaves
out metadata). Not part of a benchmark run.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import json  # noqa: E402
import sys  # noqa: E402
import functools  # noqa: E402
from collections import defaultdict  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402

from chipbench import harness as H  # noqa: E402
from chipbench import run as RUN  # noqa: E402
from chipbench import scopes as S  # noqa: E402
from chipbench import trace as T  # noqa: E402
from repro.launch.train import on_mesh  # noqa: E402
from repro.models import scopes as SC  # noqa: E402

PROGRAM = "jit_train_step"


class Recorder:
    """Hooks on the cell's driver and on the trace's reduction: the step's
    argument shapes at its first call, and the traced window's op times
    split by bucket."""

    def __init__(self):
        self.args = None
        self.trainer = None
        self.split = None
        self.outcome = None

    def hook_driver(self, mod) -> None:
        step, run = mod.Trainer.step, mod.run
        rec = self

        def running(ctx):
            rec.outcome = run(ctx)
            return rec.outcome
        mod.run = running

        def recording(self, batch):
            if rec.args is None:
                rec.trainer = self
                rec.args = jax.tree_util.tree_map(
                    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                   sharding=a.sharding),
                    (self.state["params"], self.state["opt"], batch))
            return step(self, batch)
        mod.Trainer.step = recording

    def hlo_text(self) -> str:
        prev = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            with on_mesh(self.trainer.mesh):
                return self.trainer.step_fn.lower(*self.args).compile(
                    ).as_text()
        finally:
            jax.config.update("jax_enable_compilation_cache", prev)

    def reduce_dir(self, reduce_dir):
        def reducing(trace_dir: str, chips: int) -> T.Summary:
            summary = reduce_dir(trace_dir, chips)
            tr = S.load_trace(trace_dir, chips)
            op_s = S.program_op_times(tr).get(PROGRAM, {})
            calls = summary.program_calls.get(PROGRAM, 0)
            if calls:
                self.split = split(op_s, SC.op_scopes(self.hlo_text()),
                                   calls, summary.program_s[PROGRAM])
            return summary
        return reducing


def split(op_s, op_map, calls: int, program_s: float) -> dict:
    top = defaultdict(list)
    for op, s in sorted(op_s.items(), key=lambda kv: -kv[1]):
        ops = top[op_map.get(op, "unscoped")]
        if len(ops) < S.TOP:
            ops.append([op, s / calls * 1e3])
    return {PROGRAM: {"calls": calls, "program_ms": program_s / calls * 1e3,
                      "self_ms": sum(op_s.values()) / calls * 1e3,
                      "buckets_ms": S.bucket_ms(op_s, op_map, calls),
                      "top_ops_ms": dict(top)}}


def main(argv=None, root: Path = ROOT, require_chips=H.require_chips) -> int:
    argv = list(sys.argv[1:] if argv is None else argv) + ["--trace", "1"]
    rec = Recorder()
    load = H.load_module

    def loading(path, tag):
        mod = load(path, tag)
        if tag == "driver":
            rec.hook_driver(mod)
        return mod
    saved = (H.load_module, H.Context, T.reduce_dir, RUN.T_START)
    H.load_module = loading
    H.Context = functools.partial(H.Context, control=True)
    T.reduce_dir = rec.reduce_dir(T.reduce_dir)
    RUN.T_START = T_START
    try:
        line = RUN.run(argv, root=root, require_chips=require_chips)
    except H.NoResult as e:
        print(f"chipbench: {e}; no result", file=sys.stderr, flush=True)
        return 2
    finally:
        H.load_module, H.Context, T.reduce_dir, RUN.T_START = saved
    print(line, flush=True)
    out = rec.outcome
    print(json.dumps({
        "check": out.host["check"],
        "control_correct": all(c.ok for c in out.control_checks),
        "programs": rec.split}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
