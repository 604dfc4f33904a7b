"""The converge cell's ``correct`` against its control and its faults.

Each test drives a whole run of ``g500-s21.converge`` but for the chip
check (the device is given, the CPU stands in) at scale 11 with a
half-second window: set-up, window, release and the comparison with the
float64 reference. The sound program must pass; the bfloat16 control
put in the program's place, and each fault planted in the timed path,
must come out not correct."""

import numpy as np
import pytest

from benchmark import reference, run

SCALE = 11
SEED = 2**31 + 17
DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}


@pytest.fixture
def spec(monkeypatch):
    from protocol_tpu.utils import trace

    monkeypatch.setattr(trace.TRACER, "enabled", trace.TRACER.enabled)
    s = run.cell_spec("g500-s21.converge")
    s["config"]["scale"] = SCALE
    return s


def run_once(spec):
    return run.run_cell(spec, SEED, 0.5, False, dict(DEVICE))


def wrap_converge(monkeypatch, change):
    from protocol_tpu.backend import JaxRoutedBackend

    original = JaxRoutedBackend.converge_edges

    def converge_edges(self, n, src, dst, val, valid, initial, *a, **kw):
        scores, iterations, delta = original(self, n, src, dst, val, valid,
                                             initial, *a, **kw)
        return change(n, src, dst, val, initial, scores), iterations, delta

    monkeypatch.setattr(JaxRoutedBackend, "converge_edges", converge_edges)


def test_sound_program_is_correct(spec):
    out = run_once(spec)
    assert out["correct"] is True and out["failed"] == 0
    assert out["checks"]["rel_l1"]["value"] < spec["config"]["checks"][
        "rel_l1"]
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"setup_s", "converge_s"}


def test_bf16_control_is_not_correct(spec, monkeypatch):
    cfg = spec["config"]["eigentrust"]

    def control(n, src, dst, val, initial, scores):
        low, _ = reference.eigentrust_bf16(n, src, dst, val, cfg["alpha"],
                                           initial, cfg["tol"],
                                           cfg["max_iterations"])
        return low

    wrap_converge(monkeypatch, control)
    assert run_once(spec)["correct"] is False


def test_state_returned_unchanged_is_not_correct(spec, monkeypatch):
    wrap_converge(monkeypatch, lambda n, s, d, v, initial, scores:
                  np.full_like(scores, initial))
    assert run_once(spec)["correct"] is False


def test_answer_altered_is_not_correct(spec, monkeypatch):
    def alter(n, src, dst, val, initial, scores):
        out = scores.copy()
        out[np.argmax(out)] *= 1.5
        return out

    wrap_converge(monkeypatch, alter)
    assert run_once(spec)["correct"] is False


def test_half_the_edges_left_out_is_not_correct(spec, monkeypatch):
    from protocol_tpu.ops import routed

    original = routed.build_routed_operator

    def half(n, src, dst, val, valid=None, **kw):
        k = len(src) // 2
        return original(n, src[:k], dst[:k], val[:k], valid, **kw)

    monkeypatch.setattr(routed, "build_routed_operator", half)
    assert run_once(spec)["correct"] is False
