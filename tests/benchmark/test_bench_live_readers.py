"""The live cell's per-layer readers (``benchmark/metrics/*.live.py``)
on a run of the live cell of ``attest-s19`` (then named
``attest-s20.live``) traced on a TPU v5e. A raw trace of this cell is megabytes (one
Poseidon dispatch alone is some 228,000 device operations), so what is
checked in is what ``benchmark/run.py`` hands the readers: the
reduction of the run's trace (``trace_reduce.Reduction``), the run's
per-layer counts, and the readings its result line printed (seed
2147484011, 40 s window)."""

import gzip
import json
import os

import pytest

from benchmark import metrics as readers
from benchmark import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "benchmark", "testdata",
    "live_reduction.json.gz")


def live_metrics():
    return sorted(f[:-len(".py")] for f in os.listdir(readers.HERE)
                  if f.endswith(".live.py"))


class Rec:
    """A run's record as ``benchmark/run.py`` hands it to a reader."""

    def __init__(self, trace, layer):
        self.trace = trace
        self.layer = layer
        self.config = {}
        self.device = {"kind": "TPU v5 lite"}


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(DATA, "rt") as f:
        saved = json.load(f)
    trace = saved["trace"]
    trace["programs"] = {k: tuple(v) for k, v in trace["programs"].items()}
    return Rec(tr.Reduction(**trace), saved["layer"]), saved["readings"]


def test_every_live_metric_reads_as_on_the_chip(recorded):
    rec, readings = recorded
    names = live_metrics()
    assert len(names) == 7 and set(names) <= set(readings)
    got = {m: readers.load(m)(rec) for m in names}
    assert all(v is not None for v in got.values())
    assert got == pytest.approx({m: readings[m] for m in names},
                                rel=1e-12)


def test_the_device_trace_holds_the_first_ladders_only(recorded):
    """Every window batch took the device ladder, but the profiler's
    device trace kept only the first two of its fifteen runs: per
    dispatch times read true, shares of the window would not (no idle
    share is reported for this cell)."""
    rec, _ = recorded
    _, ladders = rec.trace.program_seconds("_recover_glv")
    spans = rec.layer["spans"]
    ingests = (spans["ingest.recover_batch"]["count"]
               + spans["ingest.pipeline"]["count"])
    assert spans["service.recover_scalar"]["count"] == 0
    assert (ladders, ingests) == (2, 15)
    assert rec.trace.busy_s < 0.05 * rec.trace.window_s


@pytest.mark.parametrize("metric", live_metrics())
def test_readers_of_an_empty_run_read_nothing(metric):
    rec = Rec(None, {"spans": {}, "scopes": {}, "ingested": 0,
                     "gen_lag_ms": []})
    assert readers.load(metric)(rec) is None
