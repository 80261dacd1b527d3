"""BENCHMARK.json keeps to the benchmark's contract, and every file it
names is where the harness looks for it."""
import json
import re

import pytest

from chipbench.tests import _tiny

BENCH = json.loads((_tiny.REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["chipbench"]
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert len((_tiny.REPO / "BENCHMARK.json").read_bytes()) < 64 * 1024


def test_every_cell_has_its_files():
    base = _tiny.REPO / "chipbench"
    confs = {c["name"]: c for c in BENCH["configs"]}
    for w in BENCH["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert (_tiny.REPO / confs[w["config"]]["file"]).is_file()
        traffic = json.loads(
            (base / "traffic" / f"{w['traffic']}.json").read_text())
        assert (base / "drivers" / f"{traffic['driver']}.py").is_file()
    for m in BENCH["per_layer"]:
        assert (base / "metrics" / f"{m['name']}.py").is_file()
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(
        1, len(BENCH["workloads"]) // 2)


def test_each_cell_reports_setup_another_metric_and_a_layer():
    def reports(m, cell):
        return "workloads" not in m or cell in m["workloads"]
    for w in BENCH["workloads"]:
        e2e = {m["name"] for m in BENCH["end_to_end"] if reports(m, w["name"])}
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = [m for m in BENCH["per_layer"] if reports(m, w["name"])]
        assert layer and all(m["moves"] in e2e for m in layer)


@pytest.mark.parametrize("m", BENCH["end_to_end"], ids=lambda m: m["name"])
def test_bounds(m):
    assert 0.01 <= m["bound"] <= 0.25
    assert m["source"] in ("host_clock", "device_trace")


def test_run_length_fits_a_check_of_24_cells():
    rs = BENCH["run_seconds"]
    assert 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200
