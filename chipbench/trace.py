"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

From each ``/device:TPU:<n>`` plane: the ``XLA Ops`` line (every operation
that ran, nested inside its while loops) and the ``XLA Modules`` line (one
event per execution of a jitted program, named ``jit_<function>(<hash>)``).
From the host plane, on whichever thread's line they were recorded: the
benchmark's own ``TraceAnnotation`` spans. ``window`` marks the traced
window, and a trace without exactly one is refused; the others (``SPANS``)
say what the host was doing.

The device clock in a trace is offset from the host's by up to a few
milliseconds. Each device plane is shifted by the smallest gap between a
program's end on the device and the host's ``CompleteCallbacks`` for the same
``run_id``: the host cannot see a program finish before it has.
"""
from __future__ import annotations

import dataclasses
import glob
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

# host spans the drivers open; idle gaps are attributed to these
SPANS = ("admit", "batch_prep", "prefill", "decode", "sync", "sample")
TOP = 10


@dataclasses.dataclass
class Device:
    ops: List[Tuple[str, float, float]]       # (name, start_s, end_s)
    modules: List[Tuple[str, float, float]]   # (program, start_s, end_s)


@dataclasses.dataclass
class Trace:
    devices: List[Device]
    spans: List[Tuple[str, float, float]]     # host annotations


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                        # mean over devices
    program_s: Dict[str, float]          # device time per program, mean
    program_calls: Dict[str, int]        # executions on the first device
    device_ops: List[list]               # [[op, self seconds]], top 10
    idle_gaps: List[list]                # [[host span, seconds]], top 10


@dataclasses.dataclass
class Readings:
    """What a per-layer metric reader is given."""
    trace: Summary
    host: dict          # the driver's records of the run
    shape: dict         # the configuration's arch_config
    peaks: dict         # peaks.json entry of the device kind
    chips: int


def _op_name(full: str) -> str:
    """'%fusion.12 = bf16[..] fusion(..)' -> 'fusion.12'."""
    return full.split(" = ", 1)[0].lstrip("%")


def _program(full: str) -> str:
    """'jit_serve_step(1244..)' -> 'jit_serve_step'."""
    return full.split("(", 1)[0]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    done: Dict[Tuple[int, int], float] = {}   # (device, run_id) -> host ns
    spans, planes = [], {}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            planes[int(plane.name.rsplit(":", 1)[1])] = plane
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == "CompleteCallbacks":
                        st = dict(ev.stats)
                        if "run_id" in st:
                            key = (int(st.get("device_ordinal", 0)),
                                   int(st["run_id"]))
                            done.setdefault(key, ev.start_ns)
                    elif ev.name == "window" or ev.name in SPANS:
                        spans.append((ev.name, ev.start_ns * 1e-9,
                                      (ev.start_ns + ev.duration_ns) * 1e-9))
    devices = []
    for n in sorted(planes):
        ops, mods, lags = [], [], []
        for line in planes[n].lines:
            if line.name == "XLA Ops":
                ops = [(_op_name(e.name), e.start_ns, e.duration_ns)
                       for e in line.events]
            elif line.name == "XLA Modules":
                for e in line.events:
                    mods.append((_program(e.name), e.start_ns, e.duration_ns))
                    rid = dict(e.stats).get("run_id")
                    if rid is not None and (n, int(rid)) in done:
                        lags.append(done[(n, int(rid))]
                                    - (e.start_ns + e.duration_ns))
        shift = min(lags) if lags else 0.0
        devices.append(Device(
            ops=[(o, (s + shift) * 1e-9, (s + d + shift) * 1e-9)
                 for o, s, d in ops],
            modules=[(m, (s + shift) * 1e-9, (s + d + shift) * 1e-9)
                     for m, s, d in mods]))
    return Trace(devices=devices, spans=spans)


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def length(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    out, cur = [], lo
    for s, e in union(busy):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


def self_times(ops: Sequence[Tuple[str, float, float]]) -> Dict[str, float]:
    """Each op's time less that of the ops nested inside it (a while loop
    holds its body's ops), summed by op name."""
    out: Dict[str, float] = defaultdict(float)
    stack: List[list] = []            # [name, end, child time]
    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][1] <= s:
            n, _, child = stack.pop()
            out[n] -= child
        if stack:
            stack[-1][2] += e - s
        out[name] += e - s
        stack.append([name, e, 0.0])
    for n, _, child in stack:
        out[n] -= child
    return out


def _blame(gap: Interval, spans) -> str:
    """The host span that overlaps most of an idle gap."""
    best, name = 0.0, "host"
    for n, s, e in spans:
        ov = min(e, gap[1]) - max(s, gap[0])
        if n != "window" and ov > best:
            best, name = ov, n
    return name


def window_of(tr: Trace) -> Interval:
    """The extent of the one ``window`` span."""
    ws = [(s, e) for n, s, e in tr.spans if n == "window"]
    if len(ws) != 1:
        raise ValueError(f"trace has {len(ws)} 'window' spans, not one")
    return ws[0]


def reduce(tr: Trace, window: Optional[Interval] = None) -> Summary:
    lo, hi = window_of(tr) if window is None else window
    busy = []
    prog: Dict[str, float] = defaultdict(float)
    for dev in tr.devices:
        busy.append(length(union(clip([(s, e) for _, s, e in dev.ops],
                                      lo, hi))))
        for m, s, e in dev.modules:
            if s >= lo and e <= hi:
                prog[m] += (e - s) / len(tr.devices)
    first = tr.devices[0]
    calls: Dict[str, int] = defaultdict(int)
    for m, s, e in first.modules:
        if s >= lo and e <= hi:
            calls[m] += 1
    selfs = self_times([(n, s, e) for n, s, e in first.ops
                        if e > lo and s < hi])
    top_ops = sorted(selfs.items(), key=lambda kv: -kv[1])[:TOP]
    idle = gaps(clip([(s, e) for _, s, e in first.ops], lo, hi), lo, hi)
    idle = sorted(idle, key=lambda g: g[0] - g[1])[:TOP]
    return Summary(
        window_s=hi - lo, busy_s=sum(busy) / len(tr.devices),
        program_s=dict(prog), program_calls=dict(calls),
        device_ops=[[k, v] for k, v in top_ops],
        idle_gaps=[[_blame(g, tr.spans), g[1] - g[0]] for g in idle])


def reduce_dir(trace_dir: str, chips: int) -> Summary:
    """The summary of the one trace a run wrote under ``trace_dir``."""
    files = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}, "
                           f"found {len(files)}")
    tr = load(files[0])
    if len(tr.devices) < chips:
        raise RuntimeError(f"trace holds {len(tr.devices)} devices, "
                           f"the cell uses {chips}")
    tr.devices = tr.devices[:chips]
    return reduce(tr)
