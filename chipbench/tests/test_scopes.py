"""scopes.py: each program's op self times inside the window, split by the
model's named scopes."""
import json
from pathlib import Path

import jax
import pytest

from chipbench import scopes as S
from chipbench import trace as T
from chipbench.tests import _tiny
from repro.models import scopes as SC

FIXTURE = Path(__file__).parent / "data" / "small.xplane.pb"
NS = 1e-9


def _trace():
    """Two serve steps (a while loop around two fusions, then a copy) and a
    prefill inside the window; an op between programs; a serve step that
    runs past the window's end."""
    ops = [("while.1", 1, 5), ("fusion.1", 1, 2), ("fusion.2", 3, 5),
           ("copy.1", 5, 6),
           ("argmax.1", 6.5, 7),                      # no program
           ("while.1", 8, 11), ("fusion.1", 8, 9), ("fusion.2", 9.5, 11),
           ("copy.1", 11, 12),
           ("fusion.9", 13, 14),                      # the prefill
           ("while.1", 19, 21), ("fusion.1", 19, 20)]  # runs past 20
    mods = [("jit_serve_step", 1, 6), ("jit_serve_step", 8, 12),
            ("jit_prefill_step", 13, 14.5), ("jit_serve_step", 19, 21)]
    return T.Trace(devices=[T.Device(ops=ops, modules=mods)],
                   spans=[("window", 0, 20)])


def test_self_times_per_program_add_up_to_its_busy_time():
    tr = _trace()
    got = S.program_op_times(tr)
    assert got == {
        "jit_serve_step": {"while.1": 1 + 0.5, "fusion.1": 2, "fusion.2": 3.5,
                           "copy.1": 2},
        "jit_prefill_step": {"fusion.9": 1}}
    dev = tr.devices[0]
    for prog, op_s in got.items():
        execs = [(s, e) for m, s, e in dev.modules if m == prog and e <= 20]
        busy = T.length(T.union([(s, e) for _, s, e in dev.ops
                                 if any(a <= s < b for a, b in execs)]))
        assert sum(op_s.values()) == pytest.approx(busy)
    assert T.reduce(tr).program_calls == {"jit_serve_step": 2,
                                          "jit_prefill_step": 1}


def test_an_explicit_window_leaves_out_what_lies_outside():
    got = S.program_op_times(_trace(), window=(7, 13))
    assert got == {"jit_serve_step": {"while.1": 0.5, "fusion.1": 1,
                                      "fusion.2": 1.5, "copy.1": 1}}


def test_buckets_sum_to_the_self_time_of_a_call():
    op_s = {"while.1": 1.5e-3, "fusion.1": 2e-3, "fusion.2": 3.5e-3,
            "copy.1": 2e-3, "fusion.77": 1e-3}
    op_map = {"while.1": "layer_scan", "fusion.1": "attention",
              "fusion.2": "mlp", "copy.1": "unscoped"}    # fusion.77 absent
    got = S.bucket_ms(op_s, op_map, calls=2)
    assert list(got) == list(SC.BUCKETS)
    assert got["attention"] == pytest.approx(1.0)
    assert got["mlp"] == pytest.approx(1.75)
    assert got["layer_scan"] == pytest.approx(0.75)
    assert got["unscoped"] == pytest.approx(1.5)
    assert got["kv_write"] == got["head"] == got["norm_residual"] == 0
    assert sum(got.values()) == pytest.approx(sum(op_s.values()) / 2 * 1e3)


def test_program_op_times_on_a_recorded_v5e_trace():
    tr = T.load(str(FIXTURE))
    got = S.program_op_times(tr)
    assert list(got) == ["jit_small_step"]
    s = T.reduce(tr)
    # the union of each execution's ops is all the device did
    assert sum(got["jit_small_step"].values()) == pytest.approx(
        s.busy_s, abs=6 * NS)
    assert got["jit_small_step"]["convolution_tanh_fusion"] == \
        pytest.approx(dict(s.device_ops)["convolution_tanh_fusion"])


def test_the_tool_maps_the_programs_the_server_compiled(monkeypatch,
                                                        tmp_path):
    """The tiny cell on the CPU, whose trace has no device planes: a trace
    made of the recorded serve step's own instructions stands in."""
    texts = {}
    record = S.record_programs
    monkeypatch.setattr(S, "record_programs",
                        lambda d, t: (record(d, t), texts.update(t=t)))

    def load_trace(trace_dir, chips):
        op_map = SC.op_scopes(texts["t"]["jit_serve_step"])
        names = [next(n for n, b in op_map.items() if b == want)
                 for want in ("attention", "mlp", "layer_scan")]
        ops = [(names[2], 1, 4), (names[0], 1, 2), (names[1], 2, 4),
               ("copy.x", 4, 5)]
        mods = [("jit_serve_step", 1, 5), ("jit_other", 6, 7)]
        return T.Trace(devices=[T.Device(ops=ops, modules=mods)],
                       spans=[("window", 0, 10)])
    monkeypatch.setattr(S, "load_trace", load_trace)
    root = _tiny.make_checkout(tmp_path)
    cached = jax.config.jax_enable_compilation_cache
    try:
        out = S.measure(["--workload", "tiny-gqa.tiny", "--seed",
                         str(2**31 + 5), "--seconds", "1.5"], root=root,
                        require_chips=_tiny.cpu_chips)
        # compiled afresh: a cached program may lack the scopes
        assert not jax.config.jax_enable_compilation_cache
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
    json.dumps(out)
    assert out["correct"] is True
    assert set(texts["t"]) == {"jit_serve_step", "jit_prefill_step"}
    serve = out["programs"]["jit_serve_step"]
    assert list(out["programs"]) == ["jit_serve_step"]
    assert serve["calls"] == 1 and serve["program_ms"] == 4000
    assert serve["buckets_ms"] == dict(dict.fromkeys(SC.BUCKETS, 0.0),
                                       attention=1000, mlp=2000,
                                       layer_scan=0, unscoped=1000)
    assert serve["top_ops_ms"]["unscoped"] == [["copy.x", 1000]]
    assert [ms for _, ms in serve["top_ops_ms"]["layer_scan"]] == [0]
    # the cell's per-layer metrics, as a --trace 1 run reads them
    assert out["per_layer"] == {"tokens_served": pytest.approx(
        out["end_to_end"]["tokens_per_s"] * 1.5)}
