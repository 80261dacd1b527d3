"""What every cell shares: finding its files by name, the chip and its
peaks, the compile cache, timing spans, the result line.

A cell (``workloads`` entry of ``BENCHMARK.json``) names a configuration
(``configs/<name>.json``) and a traffic mix (``traffic/<name>.json``); the
mix names its driver (``drivers/<driver>.py``, a ``run(ctx)`` function);
each per-layer metric is read by ``metrics/<metric>.py`` (a ``read(r)``
function). Nothing here knows any cell, mix or metric by name.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import sys
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

DIR = "chipbench"           # the benchmark's directory in a checkout


class NoResult(Exception):
    """The run cannot report: no chip, too few chips, unknown device."""


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, tag: str):
    """Import one file of the benchmark by path (drivers, metric readers)."""
    if not path.is_file():
        raise NoResult(f"missing {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{tag}_{path.stem.replace('.', '_').replace('-', '_')}",
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    base: Path              # <checkout>/chipbench
    chips: int
    config: dict            # configs/<config>.json
    traffic: dict           # traffic/<mix>.json
    end_to_end: List[dict]  # the end-to-end metrics this cell reports
    per_layer: List[dict]   # the per-layer metrics this cell reports


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(root: Path, name: str) -> Cell:
    base = root / DIR
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise NoResult(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    e2e = [m for m in bench["end_to_end"] if _reports(m, name)]
    e2e_names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if _reports(m, name) and m["moves"] in e2e_names]
    return Cell(name=name, base=base, chips=int(w["chips"]),
                config=load_json(root / conf["file"]),
                traffic=load_json(base / "traffic" / f"{w['traffic']}.json"),
                end_to_end=e2e, per_layer=layer)


def arch_config(config: dict):
    """The program's ArchConfig for a configuration file: the named
    ``configs.ARCHS`` entry with every size the file gives."""
    from repro import configs
    return dataclasses.replace(configs.get(config["arch"]),
                               **config["arch_config"])


def peaks_for(base: Path, kind: str) -> dict:
    table = load_json(base / "peaks.json")
    if kind not in table:
        raise NoResult(f"device kind {kind!r} is not in peaks.json")
    return table[kind]


def require_chips(count: int) -> list:
    """The first ``count`` TPU devices; NoResult when JAX finds fewer."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoResult(f"needs a TPU; JAX found {devs[0].platform}")
    if len(devs) < count:
        raise NoResult(f"needs {count} TPU chips; JAX found {len(devs)}")
    return devs[:count]


def use_compile_cache() -> None:
    """The program's persistent compile cache, holding every program (also
    the small eager ones) so that a second run compiles nothing."""
    import jax
    from repro.launch.cache import use_compile_cache as program_cache
    program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def seed_key(seed: int):
    """A PRNG key that keeps every bit of a seed wider than 32 bits."""
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


def open_cell(root: Path, name: str, require=require_chips):
    """(cell, its chips, their peaks, its driver module), with the compile
    cache set; NoResult where the chips or their peaks are missing."""
    cell = find_cell(root, name)
    devices = require(cell.chips)
    peaks = peaks_for(cell.base, devices[0].device_kind)
    use_compile_cache()
    driver = load_module(
        cell.base / "drivers" / f"{cell.traffic['driver']}.py", "driver")
    return cell, devices, peaks, driver


def start_trace(trace_dir: str) -> None:
    """The profiler on, without Python function tracing: over seconds of a
    Python loop that fills the host tracer's buffers, and the benchmark's
    own spans (``TraceAnnotation``) are lost."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def memory_peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


@dataclasses.dataclass
class Check:
    """One number compared with its limit; ``ok`` when value <= limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclasses.dataclass
class Outcome:
    """What a driver hands back once its window has closed."""
    attempted: int
    failed: int
    end_to_end: Dict[str, float]
    checks: List[Check]
    host: Dict[str, Any]                   # records the metric readers use
    trace_dir: Optional[str] = None        # the traced window's profile
    control_checks: Optional[List[Check]] = None   # the control's, if read


@dataclasses.dataclass
class Context:
    """What a driver is given."""
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    devices: list
    peaks: dict
    t_start: float                         # process start, for setup_s
    tmp: str                               # scratch directory of this run
    control: bool = False                  # also check the fp8 control
    log: Callable[[str], None] = lambda m: print(m, file=sys.stderr,
                                                 flush=True)

    @property
    def shape(self) -> dict:
        return self.cell.config["arch_config"]


def result_line(cell: Cell, outcome: Outcome, metrics: Dict[str, float],
                device: dict, breakdown: Optional[dict]) -> str:
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    res = {
        "correct": all(c.ok for c in outcome.checks) and bool(outcome.checks),
        "attempted": outcome.attempted,
        "failed": outcome.failed,
        "metrics": {k: {"value": v, "unit": units[k]}
                    for k, v in metrics.items()},
        "device": device,
    }
    if breakdown is not None:
        res["breakdown"] = breakdown
    res["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in outcome.checks}
    return json.dumps(res)


def percentile(values: List[float], p: float) -> float:
    """Nearest-rank percentile, as ``repro.sim.stats.percentile``."""
    if not 0.0 <= p <= 100.0:
        raise ValueError(f"percentile p={p!r} out of range [0, 100]")
    if not values:
        raise ValueError("percentile of no values")
    s = sorted(values)
    return s[max(0, min(len(s) - 1, math.ceil(p / 100.0 * len(s)) - 1))]
