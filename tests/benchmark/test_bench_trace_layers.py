"""The host-span and scope readings of a trace (``benchmark/trace_layers.py``
over ``benchmark/xspace.py``) and the readers built on them, on two
small traces recorded on a TPU v5e (scale-9 converge cells): the first
from a program with neither program spans on the profiler's clock nor
named scopes, the second from one with both."""

import os

import pytest

from benchmark import metrics as readers
from benchmark import trace_layers as tl
from benchmark import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "benchmark", "testdata")
BARE = os.path.join(DATA, "converge_s9.xplane.pb.gz")
SPANS = os.path.join(DATA, "converge_s9_spans.xplane.pb.gz")
PROGRAM = "converge_routed_adaptive"
ROUTE = f"jit({PROGRAM})/while/body/jit(_apply_route_jit)"
PHASES = ("broadcast", "edge_route", "reduce", "state_route", "tail")
PROGRAM_SPANS = ("converge.stage", "converge.edges", "converge.fetch")
# the spans trace's run: its window's converges, sweeps per converge,
# and the readings its result line printed on the chip
SPANS_CONVERGES = 4
SPANS_SWEEPS = 9
SPANS_READINGS = {"stage_ms.converge": 9.27846725,
                  "fetch_ms.converge": 0.53170525,
                  "lane_perm_ms.converge": 0.0010768163888897333,
                  "relayout_ms.converge": 0.0005271223333351726}


@pytest.fixture(scope="module")
def bare():
    return tl.reduce_file(BARE)


@pytest.fixture(scope="module")
def bare_reduction():
    return tr.reduce_planes(*tr.read_planes(BARE))


def test_scopes_of_the_bare_trace(bare, bare_reduction):
    # the lane gathers and the relayouts between stages, by scope path
    assert bare.scopes[f"{ROUTE}/pallas_call:"] == pytest.approx(
        59.213904e-6, rel=1e-9)
    assert bare.scopes[f"{ROUTE}/reshape;reshape:"] == pytest.approx(
        18.218906e-6, rel=1e-9)
    assert bare.window_s == pytest.approx(bare_reduction.window_s, rel=1e-9)
    # self times by scope add up to the busy time, as by operation
    assert sum(bare.scopes.values()) == pytest.approx(bare_reduction.busy_s,
                                                      rel=1e-3)


def test_bare_trace_holds_no_program_span_or_scope(bare):
    assert bare.host_spans["bench.converge"][1] == 5
    assert not set(PROGRAM_SPANS) & set(bare.host_spans)
    assert bare.span_ms("converge.stage") is None
    for scope in ("clos.lane_perm", "clos.relayout") + PHASES:
        assert bare.scope_seconds(PROGRAM, scope) is None


class Rec:
    """A run's record as ``benchmark/run.py`` hands it to a reader, with
    its trace's readings already made."""

    def __init__(self, layers, iterations):
        self.trace = object()
        self.layers = layers
        self.layer = {"iterations": iterations}


@pytest.mark.parametrize("metric", [
    "stage_ms.converge", "fetch_ms.converge", "lane_perm_ms.converge",
    "relayout_ms.converge"])
def test_readers_leave_out_what_the_bare_trace_lacks(bare, metric):
    assert readers.load(metric)(Rec(bare, [10] * 5)) is None


@pytest.mark.parametrize("metric", [
    "stage_ms.converge", "fetch_ms.converge", "lane_perm_ms.converge",
    "relayout_ms.converge"])
def test_readers_of_an_untraced_run_read_nothing(metric):
    rec = Rec(None, [10])
    rec.trace = None
    assert readers.load(metric)(rec) is None


def test_plan_route_reader_reads_the_program_span():
    from protocol_tpu.utils import trace

    read = readers.load("plan_route_s.converge")
    before = trace.summary().get("routed.plan.route", {}).get("total_s")
    assert read(Rec(None, [])) == before
    was = trace.TRACER.enabled
    trace.enable()
    try:
        with trace.span("routed.plan.route"):
            pass
    finally:
        if not was:
            trace.disable()
    after = read(Rec(None, []))
    assert after is not None and after >= (before or 0.0)


def hand_made_space():
    """Host: the window [0, 1000) ns and two spans, one cut by the
    window's end. Device: a ``while`` [100, 600) holding a lane gather
    [200, 300) whose scope is stored inline, and a relayout [400, 450)
    whose scope is a reference to a stat metadata's name."""
    from benchmark import xspace

    space = xspace.XSpace()
    host = space.planes.add(name=tr.HOST_PLANE)
    for key, name in enumerate([tr.WINDOW, "converge.stage",
                                "converge.fetch"], start=1):
        host.event_metadata.add(key=key).value.name = name
    line = host.lines.add(name="python", timestamp_ns=0)
    for mid, start, length in [(1, 0, 1000), (2, 50, 100), (3, 900, 200)]:
        line.events.add(metadata_id=mid, offset_ps=start * 1000,
                        duration_ps=length * 1000)
    dev = space.planes.add(name=tr.DEVICE_PREFIX + "0")
    dev.stat_metadata.add(key=7).value.name = tl.SCOPE_STAT
    dev.stat_metadata.add(key=8).value.name = "jit(f)/edge_route/clos.relayout/t"
    lane = dev.event_metadata.add(key=2).value
    lane.stats.add(metadata_id=7, str_value="jit(f)/edge_route/clos.lane_perm/p")
    dev.event_metadata.add(key=3).value.stats.add(metadata_id=7, ref_value=8)
    ops = dev.lines.add(name=tr.OPS_LINE, timestamp_ns=0)
    for mid, start, length in [(1, 100, 500), (2, 200, 100), (3, 400, 50)]:
        ops.events.add(metadata_id=mid, offset_ps=start * 1000,
                       duration_ps=length * 1000)
    return space


def test_hand_made_space():
    layers = tl.reduce_space(hand_made_space())
    assert layers.window_s == pytest.approx(1000e-9)
    assert layers.host_spans["converge.stage"] == (pytest.approx(100e-9), 1)
    assert layers.host_spans["converge.fetch"] == (pytest.approx(100e-9), 1)
    assert layers.span_ms("converge.stage") == pytest.approx(100e-6)
    assert layers.scopes == pytest.approx({
        tl.NO_SCOPE: 350e-9,
        "jit(f)/edge_route/clos.lane_perm/p": 100e-9,
        "jit(f)/edge_route/clos.relayout/t": 50e-9})
    assert layers.scope_seconds("f", "clos.lane_perm") == pytest.approx(
        100e-9)
    assert layers.scope_seconds("f", "edge_route") == pytest.approx(150e-9)
    assert layers.scope_seconds("f", "clos") is None
    assert layers.scope_seconds("g", "clos.lane_perm") is None


# --- the trace of a program with spans and scopes ---------------------------


@pytest.fixture(scope="module")
def spans():
    return tl.reduce_file(SPANS)


@pytest.fixture(scope="module")
def spans_reduction():
    return tr.reduce_planes(*tr.read_planes(SPANS))


def test_program_spans_once_per_window_converge(spans):
    converges = spans.host_spans["bench.converge"][1]
    assert converges == SPANS_CONVERGES
    for name in PROGRAM_SPANS:
        assert spans.host_spans[name][1] == converges, name


def test_scoped_device_time_lies_within_the_program(spans, spans_reduction):
    program_s, runs = spans_reduction.program_seconds(PROGRAM)
    assert runs == SPANS_CONVERGES
    scoped = sum(s for path, s in spans.scopes.items()
                 if path.startswith(f"jit({PROGRAM})/"))
    phases = [spans.scope_seconds(PROGRAM, p) for p in PHASES]
    assert all(s is not None and s > 0 for s in phases)
    assert sum(phases) <= scoped <= program_s
    clos = (spans.scope_seconds(PROGRAM, "clos.lane_perm")
            + spans.scope_seconds(PROGRAM, "clos.relayout"))
    routes = (spans.scope_seconds(PROGRAM, "edge_route")
              + spans.scope_seconds(PROGRAM, "state_route"))
    assert 0 < clos <= routes


def test_largest_idle_gap_goes_to_a_program_span(spans_reduction):
    assert spans_reduction.breakdown()["idle_gaps"][0][0] in PROGRAM_SPANS


def test_readers_on_the_spans_trace(spans):
    rec = Rec(spans, [SPANS_SWEEPS] * SPANS_CONVERGES)
    got = {m: readers.load(m)(rec) for m in SPANS_READINGS}
    assert got == pytest.approx(SPANS_READINGS, rel=1e-9)
