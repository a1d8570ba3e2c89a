"""The reduction from trace events to the per-layer numbers."""
import os

import pytest

from chipbench import trace

DATA = os.path.join(os.path.dirname(__file__), "data")
DEV = "/device:TPU:0"


def _ev(kind, name, start_us, end_us, track=DEV):
    return (kind, name, int(start_us * 1000), int(end_us * 1000), track)


def hand_trace():
    """A 100 µs window: two programs, four ops (two overlapping), three
    idle gaps under different harness spans."""
    return [
        _ev("span", "cb:window", 0, 100, "python3"),
        _ev("span", "cb:factor_batched", 0, 40, "python3"),
        _ev("span", "cb:solve_batched", 40, 100, "python3"),
        _ev("module", "jit__refactor", 10, 30),
        _ev("op", "fusion.1", 10, 20),
        _ev("op", "scatter.2", 15, 30),      # overlaps fusion.1
        _ev("module", "jit_solve_refined", 50, 90),
        _ev("op", "while.3", 50, 70),
        _ev("op", "while.3", 80, 90),
        _ev("op", "fusion.1", 120, 130),     # outside the window
    ]


def test_reduce_hand_trace():
    s = trace.reduce(hand_trace())
    assert s.window_s == pytest.approx(100e-6)
    # busy: [10, 30] ∪ [50, 70] ∪ [80, 90] = 50 µs
    assert s.busy_s == pytest.approx(50e-6)
    assert s.idle_share == pytest.approx(0.5)
    assert s.program("_refactor") == (pytest.approx(20e-6), 1)
    assert s.program("solve_refined") == (pytest.approx(40e-6), 1)
    assert s.op_s["while.3"] == pytest.approx(30e-6)
    assert s.op_s["fusion.1"] == pytest.approx(10e-6)
    # gaps: [0,10] factor; [30,50], labelled at its midpoint 40 where the
    # solve span opens; [70,80] and [90,100] solve
    assert s.gaps[0] == ("solve_batched", pytest.approx(20e-6))
    labels = sorted(g[0] for g in s.gaps)
    assert labels == ["factor_batched"] + ["solve_batched"] * 3
    assert sum(g[1] for g in s.gaps) == pytest.approx(50e-6)
    b = s.breakdown()
    assert b["device_ops"][0] == ["while.3", pytest.approx(30e-6)]
    assert len(b["idle_gaps"]) == 4


def test_reduce_without_window_span_uses_device_extent():
    ev = [e for e in hand_trace() if e[1] != "cb:window"]
    s = trace.reduce(ev)
    assert s.window_s == pytest.approx(120e-6)      # 10 µs .. 130 µs
    assert s.busy_s == pytest.approx(60e-6)


def test_reduce_averages_devices():
    ev = hand_trace() + [_ev("op", "fusion.1", 0, 100, "/device:TPU:1"),
                         _ev("module", "jit__refactor", 0, 100,
                             "/device:TPU:1")]
    s = trace.reduce(ev)
    assert s.n_devices == 2
    assert s.busy_s == pytest.approx(75e-6)
    assert s.program("_refactor")[0] == pytest.approx(60e-6)


def test_reduce_refuses_a_trace_without_device_events():
    with pytest.raises(ValueError):
        trace.reduce([_ev("span", "cb:window", 0, 1, "python3")])


def test_recorded_chip_trace(tmp_path):
    """A small trace recorded on a TPU v5e (circuit-sweep's programs at
    n=200, K=4, two steps): the reduction finds both programs once a step,
    a busy time inside the window, and gaps labelled by harness spans."""
    ev = trace.load_events_json(os.path.join(DATA, "v5e_small_events.json"))
    s = trace.reduce(ev)
    assert s.n_devices == 1
    assert 0 < s.busy_s < s.window_s
    assert s.program("_refactor")[1] == 2
    assert s.program("solve_refined")[1] == 2
    assert all(label for label, _ in s.gaps)
    p = tmp_path / "ev.json"
    trace.save_events(ev, str(p))
    assert trace.reduce(trace.load_events_json(str(p))) == s
