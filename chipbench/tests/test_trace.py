"""trace.py's arithmetic on a trace recorded on a TPU v5e: three executions
of a 512x512 bf16 matmul program (``jit_small_step``), each dispatched in a
``decode`` span and read back in a ``sync`` span, inside a ``window``."""
from pathlib import Path

import pytest

from chipbench import trace as T

FIXTURE = Path(__file__).parent / "data" / "small.xplane.pb"
NS = 1e-9


@pytest.fixture(scope="module")
def tr():
    return T.load(str(FIXTURE))


def test_planes_and_spans(tr):
    assert len(tr.devices) == 1
    names = [n for n, _, _ in tr.spans]
    assert names.count("window") == 1
    assert names.count("decode") == 3 and names.count("sync") == 3


def test_clock_shift_puts_programs_after_their_dispatch(tr):
    # host CompleteCallbacks minus program end, smallest of runs 4, 5, 6
    shift = 52595717 - (50908457 + 2426)
    starts = [s for _, s, _ in tr.devices[0].modules]
    assert starts[0] == pytest.approx((45923290 + shift) * NS, abs=2 * NS)
    decode = [s for n, s, _ in tr.spans if n == "decode"]
    assert all(d <= s for d, s in zip(decode, starts))


def test_busy_idle_and_programs(tr):
    s = T.reduce(tr)
    assert s.window_s == pytest.approx(12526589 * NS, abs=2 * NS)
    # union of copy-start, copy-done and the fusion in each execution
    busy = (13 + 3 + 2402) + (14 + 3 + 2404) + (13 + 3 + 2403)
    assert s.busy_s == pytest.approx(busy * NS, abs=6 * NS)
    assert s.program_calls == {"jit_small_step": 3}
    assert s.program_s["jit_small_step"] == pytest.approx(
        (2425 + 2426 + 2426) * NS, abs=3 * NS)
    idle = sum(g for _, g in s.idle_gaps)
    assert idle == pytest.approx(s.window_s - s.busy_s, rel=1e-6)
    assert {n for n, _ in s.idle_gaps} <= {"decode", "sync", "host"}
    ops = dict(s.device_ops)
    assert ops["convolution_tanh_fusion"] == pytest.approx(
        (2402 + 2404 + 2403) * NS, abs=6 * NS)


def test_interval_arithmetic():
    assert T.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert T.clip([(0, 2), (3, 5), (6, 7)], 1, 4) == [(1, 2), (3, 4)]
    assert T.gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]


def test_self_time_of_nested_ops():
    ops = [("while.1", 0, 10), ("fusion.1", 1, 3), ("fusion.2", 4, 8),
           ("fusion.1", 12, 13)]
    st = T.self_times(ops)
    assert st == {"while.1": 4, "fusion.1": 3, "fusion.2": 4}


def test_busy_counts_nested_ops_once_and_whole_programs_only():
    dev = T.Device(ops=[("while.1", 0, 10), ("fusion.1", 0, 4),
                        ("fusion.2", 5, 9), ("fusion.3", 11, 12)],
                   modules=[("jit_serve_step", 0, 10),
                            ("jit_serve_step", 11, 13)])
    s = T.reduce(T.Trace(devices=[dev], spans=[]), window=(0, 12))
    assert s.busy_s == 11 and s.window_s == 12
    assert s.program_calls == {"jit_serve_step": 1}     # 11-13 runs past
    assert s.program_s == {"jit_serve_step": 10}
    assert [g for _, g in s.idle_gaps] == [1]


def test_a_trace_without_its_window_span_is_refused(tr):
    spans = [sp for sp in tr.spans if sp[0] != "window"]
    with pytest.raises(ValueError, match="0 'window' spans"):
        T.reduce(T.Trace(devices=tr.devices, spans=spans))
    with pytest.raises(ValueError, match="2 'window' spans"):
        T.reduce(T.Trace(devices=tr.devices, spans=tr.spans + [
            sp for sp in tr.spans if sp[0] == "window"]))
