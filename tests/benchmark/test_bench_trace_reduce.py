"""The trace reduction, on a small trace recorded on a TPU v5e (a
scale-9 converge cell, five converges in a 0.06 s window) and on
hand-made intervals."""

import os

import pytest

from benchmark import trace_reduce as tr

TRACE = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "benchmark", "testdata",
    "converge_s9.xplane.pb.gz")


@pytest.fixture(scope="module")
def recorded():
    return tr.reduce_planes(*tr.read_planes(TRACE))


def test_recorded_busy_and_window(recorded):
    assert recorded.chips == 1
    assert recorded.window_s == pytest.approx(0.061356315, rel=1e-9)
    assert recorded.busy_s == pytest.approx(0.000602459, rel=1e-9)
    assert recorded.idle_share == pytest.approx(1 - 0.000602459 / 0.061356315)


def test_recorded_programs(recorded):
    seconds, runs = recorded.program_seconds("converge_routed_adaptive")
    assert runs == 5
    assert seconds == pytest.approx(0.000593549, rel=1e-9)
    assert recorded.program_seconds("no_such_program") == (0.0, 0)


def test_recorded_breakdown(recorded):
    b = recorded.breakdown()
    assert len(b["device_ops"]) == tr.TOP and len(b["idle_gaps"]) <= tr.TOP
    assert b["idle_gaps"][0][0] == "bench.converge"
    # self times of the operations add up to the busy time
    assert sum(recorded.ops.values()) == pytest.approx(recorded.busy_s,
                                                       rel=1e-6)
    assert sum(recorded.gaps.values()) == pytest.approx(
        recorded.window_s - recorded.busy_s, rel=1e-6)


def hand_made():
    I = tr.Interval
    ops = [I("%while.3 = x", 0, 100), I("%a = y", 10, 30),
           I("%b = z", 40, 90), I("%c = q", 50, 60),
           I("%d = r", 120_000, 150_000)]
    device = {"/device:TPU:0": {
        tr.OPS_LINE: ops,
        tr.MODULES_LINE: [I("jit_f(1)", 0, 100), I("jit_g(2)", 120_000,
                                                     150_000)]}}
    host = [I(tr.WINDOW, -50, 200_000), I("bench.outer", -40, 190_000),
            I("stage", 100, 119_000)]
    return tr.reduce_planes(device, host)


def test_self_times_of_nested_ops():
    r = hand_made()
    assert r.ops == pytest.approx({"while.3": 30e-9, "a": 20e-9, "b": 40e-9,
                                   "c": 10e-9, "d": 30_000e-9})
    assert r.busy_s == pytest.approx(30_100e-9)
    assert r.programs["jit_f(1)"] == (pytest.approx(100e-9), 1)


def test_gaps_go_to_the_shortest_covering_host_event():
    r = hand_made()
    # [-50, 0) is short; [100, 120000) lies in "stage" over half its
    # length; [150000, 200000) only in the outer span
    assert r.gaps[tr.SHORT_GAPS] == pytest.approx(50e-9)
    assert r.gaps["stage"] == pytest.approx(119_900e-9)
    assert r.gaps["bench.outer"] == pytest.approx(50_000e-9)


def test_window_must_be_marked_once():
    with pytest.raises(ValueError):
        tr.reduce_planes({}, [])
