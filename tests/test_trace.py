"""Unit tests for the instrumentation layer (``utils/trace.py``) and
its Prometheus rendering (``service/metrics.py``): typed instruments,
histogram bucket math, label sanitization, counter monotonicity across
``reset()``, trace-context propagation, the thread-safety fixes
(serialized emits, locked dumps, epoch span starts), and the
exposition-format lint."""

import json
import threading

import pytest

from protocol_tpu.service.metrics import lint_exposition, render_prometheus
from protocol_tpu.utils import trace


@pytest.fixture()
def tracer():
    """A clean, enabled process tracer; full teardown afterwards so no
    instrument or span leaks into other tests."""
    trace.TRACER.reset()
    trace.TRACER.reset_instruments()
    was_enabled = trace.TRACER.enabled
    trace.TRACER.enable()
    yield trace.TRACER
    trace.TRACER.reset()
    trace.TRACER.reset_instruments()
    # the compile tracker (and its steady-recompile latch) is
    # process-global state some tests deliberately trip
    trace.TRACER.compile_tracker.reset()
    if not was_enabled:
        trace.TRACER.disable()


# --- typed instruments ------------------------------------------------------


def test_histogram_bucket_math(tracer):
    h = trace.histogram("bucket_math_seconds", buckets=(0.001, 0.01, 0.1))
    # boundary value lands in ITS bucket (le is inclusive), overflow in
    # +Inf, and count/sum are exact — not bucket-approximated
    for v in (0.0005, 0.001, 0.002, 0.05, 99.0):
        h.observe(v)
    ((_, s),) = h.series()
    assert s["counts"] == [2, 1, 1, 1]  # ≤1ms: 2, ≤10ms: 1, ≤100ms: 1, +Inf: 1
    assert s["count"] == 5
    assert s["sum"] == pytest.approx(0.0005 + 0.001 + 0.002 + 0.05 + 99.0)

    page = render_prometheus()
    assert "# TYPE ptpu_bucket_math_seconds histogram" in page
    # cumulative buckets with the +Inf terminator equal to _count
    assert 'ptpu_bucket_math_seconds_bucket{le="0.001"} 2' in page
    assert 'ptpu_bucket_math_seconds_bucket{le="0.01"} 3' in page
    assert 'ptpu_bucket_math_seconds_bucket{le="0.1"} 4' in page
    assert 'ptpu_bucket_math_seconds_bucket{le="+Inf"} 5' in page
    assert "ptpu_bucket_math_seconds_count 5" in page
    assert lint_exposition(page) == []


def test_histogram_default_buckets_are_log_spaced():
    b = trace.DEFAULT_BUCKETS
    assert b[0] == pytest.approx(1e-4)
    assert b[-1] == pytest.approx(100.0)
    ratios = [b[i + 1] / b[i] for i in range(len(b) - 1)]
    for r in ratios:
        assert r == pytest.approx(10 ** 0.5, rel=1e-6)


def test_label_sanitization_and_escaping(tracer):
    c = trace.counter("weird.name-x")
    c.inc(1, **{"end point": '/score/"0x\nabc"'})
    page = render_prometheus()
    # dots/dashes/spaces become underscores; quote + newline escape
    assert "# TYPE ptpu_weird_name_x_total counter" in page
    assert 'end_point="/score/\\"0x\\nabc\\""' in page
    assert lint_exposition(page) == []


def test_counter_monotonic_across_reset(tracer):
    c = trace.counter("monotonic_things")
    c.inc()
    c.inc(2)
    assert c.value() == 3.0
    trace.TRACER.reset()  # clears spans/events/metric histories...
    assert c.value() == 3.0, "reset() must not rewind a counter"
    c.inc()
    assert c.value() == 4.0
    assert trace.counter("monotonic_things") is c  # registry survives
    with pytest.raises(ValueError):
        c.inc(-1)
    with pytest.raises(ValueError):
        trace.gauge("monotonic_things")  # type conflict is an error


def test_counter_set_total_clamps(tracer):
    c = trace.counter("adopted_total")
    c.set_total(7)
    c.set_total(5)  # external totals may be re-read out of order
    assert c.value() == 7.0


def test_monotonic_legacy_metrics_render_as_counters(tracer):
    trace.metric("service.rpc_retries", 3)
    trace.metric("service.refresh_total", 5)
    trace.metric("service.block_cursor", 9)
    page = render_prometheus()
    # cumulative series: a real counter with _total...
    assert "# TYPE ptpu_service_rpc_retries_total counter" in page
    # ... with the old gauge name kept as a deprecated alias
    assert "# TYPE ptpu_service_rpc_retries gauge" in page
    # names already ending _total migrate IN PLACE (no _total_total)
    assert "# TYPE ptpu_service_refresh_total counter" in page
    assert "ptpu_service_refresh_total_total" not in page
    # genuinely instantaneous series stay gauges
    assert "# TYPE ptpu_service_block_cursor gauge" in page
    assert "ptpu_service_block_cursor_total" not in page
    # span aggregates: counts/cumulative-seconds are counters now
    with trace.span("x"):
        pass
    page = render_prometheus()
    assert "# TYPE ptpu_span_total counter" in page
    assert "# TYPE ptpu_span_seconds_total counter" in page
    assert "# TYPE ptpu_span_count gauge" in page  # alias, one release
    assert lint_exposition(page) == []


# --- trace-context propagation ----------------------------------------------


def test_trace_context_propagation(tmp_path, tracer):
    stream = tmp_path / "trace.jsonl"
    trace.TRACER.enable(str(stream))
    with trace.context(trace_id="att-0123456789abcdef"):
        with trace.span("stage.a"):
            with trace.span("stage.b"):
                trace.event("stage.mark", note=1)
    with trace.span("unrelated"):
        pass
    trace.TRACER.disable()
    trace.TRACER.enabled = True  # keep the fixture's enabled state

    records = [json.loads(line) for line in
               stream.read_text().splitlines()]
    assert all(trace.validate_record(r) is None for r in records)
    spans = {r["name"]: r for r in records if r["type"] == "span"}
    a, b = spans["stage.a"], spans["stage.b"]
    # one synthetic work item → a joinable chain: shared trace id,
    # parent/child span ids
    assert a["trace_id"] == b["trace_id"] == "att-0123456789abcdef"
    assert b["parent_id"] == a["span_id"]
    assert "parent_id" not in a
    event = next(r for r in records if r["type"] == "event")
    assert event["trace_id"] == "att-0123456789abcdef"
    assert "trace_id" not in spans["unrelated"]
    # epoch start: span ts aligns with the event's wall-clock timeline
    assert abs(a["ts"] - event["ts"]) < 60.0


def test_trace_context_batch_ids(tracer):
    with trace.context(trace_ids=["id1", "id2"]):
        assert trace.current_trace_ids() == ("id1", "id2")
        with trace.span("batch.stage"):
            pass
    assert trace.current_trace_ids() == ()
    rec = trace.TRACER.spans[-1]
    assert rec.trace_ids == ("id1", "id2")


def test_pending_traces_revision_handoff():
    p = trace.PendingTraces(cap=8)
    p.add(1, ["a"])
    p.add(2, ["b", "c"])
    p.add(5, ["d"])
    assert p.take(2) == ["a", "b", "c"]
    assert p.take(2) == []  # drained
    assert p.take(10) == ["d"]
    # bounded: overflow drops oldest, never grows without bound
    for r in range(20):
        p.add(r, [f"x{r}"])
    assert len(p.take(100)) <= 8


def test_dump_and_emit_are_thread_safe(tmp_path, tracer):
    """Concurrent span emission during dump_jsonl must neither crash
    nor interleave partial JSONL lines in the stream."""
    stream = tmp_path / "stream.jsonl"
    trace.TRACER.enable(str(stream))
    stop = threading.Event()

    def hammer(k):
        while not stop.is_set():
            with trace.span(f"hammer.{k}", payload="x" * 64):
                pass

    threads = [threading.Thread(target=hammer, args=(k,), daemon=True)
               for k in range(4)]
    for t in threads:
        t.start()
    try:
        for _ in range(20):
            trace.TRACER.dump_jsonl(str(tmp_path / "dump.jsonl"))
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=5)
    trace.TRACER.disable()
    trace.TRACER.enabled = True
    for path in (stream, tmp_path / "dump.jsonl"):
        for line in path.read_text().splitlines():
            json.loads(line)  # raises on an interleaved/torn line


# --- exposition lint --------------------------------------------------------


def test_lint_exposition_catches_malformations():
    assert lint_exposition(
        "# TYPE ok_total counter\nok_total 3\n") == []
    # counter without _total suffix
    assert any("_total" in e for e in lint_exposition(
        "# TYPE bad counter\nbad 3\n"))
    # sample without a TYPE declaration
    assert any("TYPE" in e for e in lint_exposition("orphan 1\n"))
    # duplicate series
    assert any("duplicate" in e for e in lint_exposition(
        "# TYPE g gauge\ng 1\ng 2\n"))
    # non-cumulative histogram buckets
    page = ("# TYPE h histogram\n"
            'h_bucket{le="0.1"} 5\n'
            'h_bucket{le="+Inf"} 3\n'
            "h_sum 1.0\nh_count 3\n")
    assert any("cumulative" in e for e in lint_exposition(page))
    # +Inf bucket disagreeing with _count
    page = ("# TYPE h histogram\n"
            'h_bucket{le="0.1"} 1\n'
            'h_bucket{le="+Inf"} 2\n'
            "h_sum 1.0\nh_count 3\n")
    assert any("_count" in e for e in lint_exposition(page))
    # unparseable garbage
    assert any("unparseable" in e for e in lint_exposition(
        "# TYPE g gauge\ng{ 1\n"))


def test_validate_record():
    ok = {"type": "span", "name": "a", "duration_s": 0.1}
    assert trace.validate_record(ok) is None
    assert trace.validate_record({"type": "nope", "name": "a"})
    assert trace.validate_record({"type": "span", "name": ""})
    assert trace.validate_record(
        {"type": "span", "name": "a", "duration_s": "fast"})
    assert trace.validate_record(
        {"type": "metric", "name": "m", "value": "high"})
    assert trace.validate_record(
        {"type": "metric", "name": "m", "values": [1, 2]}) is None


# --- sync-span mode ----------------------------------------------------------


def test_sync_spans_mode_toggles_and_syncs(tracer):
    jnp = pytest.importorskip("jax.numpy")

    assert not trace.sync_enabled()
    trace.sync_spans(True)
    try:
        assert trace.sync_enabled()
        x = jnp.arange(8) * 2
        # must block-and-return the value, never raise — host values and
        # pytrees included
        assert trace.device_sync(x) is x
        assert trace.device_sync([x, x]) is not None
        assert trace.device_sync(None) is None
        assert trace.device_sync("host value") == "host value"
    finally:
        trace.sync_spans(False)
    assert not trace.sync_enabled()


def test_device_sync_noop_when_disabled(tracer):
    # sync mode off: no jax import, no blocking — identity passthrough
    sentinel = object()
    assert trace.device_sync(sentinel) is sentinel


# --- percentile stage summaries ---------------------------------------------


def test_percentile_nearest_rank():
    vals = [0.1, 0.2, 0.3, 0.4, 1.0]
    assert trace.percentile(vals, 0.5) == 0.3
    assert trace.percentile(vals, 0.95) == 1.0
    assert trace.percentile([7.0], 0.5) == 7.0
    with pytest.raises(ValueError):
        trace.percentile([], 0.5)


def test_stage_summary_percentiles(tracer):
    import time as _time

    for _ in range(4):
        with trace.span("stage.sleepy"):
            _time.sleep(0.002)
    s = trace.stage_summary()["stage.sleepy"]
    assert s["count"] == 4
    assert s["total_s"] >= 0.008
    assert 0.0 < s["p50_s"] <= s["p95_s"] <= s["max_s"]


# --- XLA compile tracking ----------------------------------------------------


def test_compile_tracking_counts_and_steady_recompile_latch(tracer):
    jax = pytest.importorskip("jax")
    jnp = pytest.importorskip("jax.numpy")

    tracker = trace.TRACER.compile_tracker
    assert trace.install_compile_tracking()

    def fresh_jit():
        # a NEW jit wrapper each call: same shapes, yet XLA must
        # recompile — the model of a leaking jit cache
        @jax.jit
        def f(x):
            return x * 3 + 1

        return f

    sig = ("test-steady", 8)
    base = tracker.stats()["steady_recompiles"]
    with trace.compile_watch("testsite", signature=sig):
        fresh_jit()(jnp.ones(8)).block_until_ready()
    first = tracker.stats()
    assert trace.TRACER.counter("xla_compiles").value(site="testsite") >= 1
    # first sighting of the signature: compiles are legit, no latch
    assert first["steady_recompiles"] == base

    with trace.compile_watch("testsite", signature=sig):
        fresh_jit()(jnp.ones(8)).block_until_ready()
    second = tracker.stats()
    assert second["steady_recompiles"] > base
    assert second["recompile_warning"] is True
    assert trace.TRACER.counter("xla_steady_recompiles").value(
        site="testsite") >= 1


def test_compile_watch_cache_hit_does_not_latch(tracer):
    jax = pytest.importorskip("jax")
    jnp = pytest.importorskip("jax.numpy")

    trace.install_compile_tracking()

    @jax.jit
    def g(x):
        return x + 2

    sig = ("test-hit", 16)
    tracker = trace.TRACER.compile_tracker
    with trace.compile_watch("hitsite", signature=sig):
        g(jnp.ones(16)).block_until_ready()
    before = tracker.stats()["steady_recompiles"]
    with trace.compile_watch("hitsite", signature=sig):
        # same jitted callable, same shape: jit cache hit, no compile,
        # and crucially NO steady-recompile latch
        g(jnp.ones(16)).block_until_ready()
    after = tracker.stats()
    assert after["steady_recompiles"] == before


def test_compile_stats_shape(tracer):
    stats = trace.compile_stats()
    for key in ("installed", "compiles", "compile_seconds", "cache_hits",
                "steady_recompiles", "recompile_warning", "last_site"):
        assert key in stats


_CACHE_PROBE = """
import json, sys
import jax, jax.numpy as jnp
from protocol_tpu.utils import trace
jax.config.update("jax_enable_compilation_cache", True)
jax.config.update("jax_compilation_cache_dir", sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
trace.enable()
trace.install_compile_tracking()
with trace.compile_watch("probe"):
    jax.jit(lambda x: jnp.sin(x) * 3 + 1)(jnp.arange(8.0)).block_until_ready()
stats = trace.compile_stats()
print(json.dumps({"compiles": stats["compiles"],
                  "cache_hits": stats["cache_hits"],
                  "hits_counter": trace.counter_total("xla_cache_hits",
                                                      site="probe"),
                  "compiles_counter": trace.counter_total("xla_compiles",
                                                          site="probe")}))
"""


def test_compile_tracker_counts_cache_loads_apart(tmp_path):
    """A persistent-cache load fires JAX's compile-duration event too:
    the first process compiles (and writes the cache), a fresh one with
    the same cache directory only loads — a hit, not a compile."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = tmp_path / "probe.py"
    script.write_text(_CACHE_PROBE)
    cache = tmp_path / "xla"

    def run():
        out = subprocess.run(
            [sys.executable, str(script), str(cache)], cwd=repo,
            env=dict(os.environ, PYTHONPATH=repo), capture_output=True,
            text=True, timeout=120, check=True)
        return json.loads(out.stdout.strip().splitlines()[-1])

    first = run()
    assert first["compiles"] >= 1 and first["cache_hits"] == 0
    assert first["compiles_counter"] == first["compiles"]
    assert first["hits_counter"] == 0
    second = run()
    assert second["compiles"] == 0
    assert second["cache_hits"] == first["compiles"]
    assert second["hits_counter"] == second["cache_hits"]
    assert second["compiles_counter"] == 0


# --- spans on the profiler's clock ------------------------------------------


def _host_events(log_dir) -> dict:
    """{event name: [(start ns, end ns)]} of the host plane of the one
    trace under ``log_dir``."""
    import glob
    import os

    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(str(log_dir), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    out: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    out.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns))
    return out


def _traced_spans(log_dir):
    import jax

    jax.profiler.start_trace(str(log_dir))
    try:
        # span fields stay out of the profiler event's name
        with trace.span("t.outer", size=4):
            with trace.span("t.inner"):
                jax.numpy.ones(4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    return _host_events(log_dir)


def test_spans_land_on_the_profiler_host_plane(tracer, tmp_path):
    pytest.importorskip("jax")
    events = _traced_spans(tmp_path / "on")
    ((o0, o1),) = events["t.outer"]
    ((i0, i1),) = events["t.inner"]
    assert o0 <= i0 <= i1 <= o1
    trace.TRACER.disable()
    try:
        events = _traced_spans(tmp_path / "off")
    finally:
        trace.TRACER.enable()
    assert "t.outer" not in events and "t.inner" not in events


# --- converge instrumentation ------------------------------------------------


ROUTED_SCOPES = ("broadcast", "edge_route", "reduce", "state_route", "tail",
                 "clos.lane_perm", "clos.relayout")


def test_routed_sweep_scopes_reach_the_optimized_hlo():
    """The named scopes of the routed sweep survive into the compiled
    program's op metadata, where a profiler trace reads them. The edge
    route has three levels (2^15 slots), so a shuffle between its outer
    stages remains for ``clos.relayout``: a two-level route is all
    inner level."""
    import re

    jnp = pytest.importorskip("jax.numpy")
    from protocol_tpu.graph import barabasi_albert_edges
    from protocol_tpu.ops.routed import (
        build_routed_operator,
        converge_routed_adaptive,
        routed_arrays,
    )

    n = 2000
    src, dst, val = barabasi_albert_edges(n, 4, seed=1)
    op = build_routed_operator(n, src, dst, val)
    assert len(op.edge_bits) == 3
    arrs, static = routed_arrays(op, alpha=0.1)
    s0 = jnp.asarray(op.initial_scores(1000.0))
    text = converge_routed_adaptive.lower(
        arrs, static, s0, tol=1e-6, max_iterations=50).compile().as_text()
    paths = [p.split("/") for p in set(re.findall(r'op_name="([^"]*)"', text))]
    for scope in ROUTED_SCOPES:
        held = [p for p in paths if scope in p]
        assert held, scope
        assert all(p[0] == "jit(converge_routed_adaptive)" for p in held)


@pytest.mark.parametrize("backend,entry", [
    ("JaxSparseBackend", "converge_edges"),
    ("JaxRoutedBackend", "converge_edges"),
    ("JaxSparseBackend", "converge_topics"),
    ("JaxRoutedBackend", "converge_topics"),
])
def test_converge_spans_stage_edges_fetch(tracer, backend, entry):
    """Every converge entry point runs its host phases under the same
    three spans; the routed build adds its plan phases."""
    np = pytest.importorskip("numpy")
    from protocol_tpu import backend as backends
    from protocol_tpu.graph import barabasi_albert_edges

    n = 200
    src, dst, val = barabasi_albert_edges(n, 3, seed=5)
    valid = np.ones(n, dtype=bool)
    be = getattr(backends, backend)()
    if entry == "converge_edges":
        be.converge_edges(n, src, dst, val, valid, 1000.0, 100, tol=1e-6,
                          alpha=0.1)
    else:
        be.converge_topics(n, src, dst, val, valid,
                           np.full((2, n), 1000.0), 100, alpha=0.1)
    spans = trace.summary()
    for name in ("converge.stage", "converge.edges", "converge.fetch"):
        assert spans[name]["count"] == 1, name
    plan = ("routed.plan_build", "routed.plan.filter",
            "routed.plan.bucketize", "routed.plan.assemble",
            "routed.plan.route", "routed.plan.finish")
    if backend == "JaxRoutedBackend":
        children = sum(spans[p]["total_s"] for p in plan[1:])
        assert all(spans[p]["count"] == 1 for p in plan)
        assert children <= spans["routed.plan_build"]["total_s"]
    else:
        assert not set(plan) & set(spans)


def test_routed_converge_twice_stages_once(tracer):
    """Two converges on one routed operator: each runs the three spans,
    and only the first stages the operator."""
    np = pytest.importorskip("numpy")
    from protocol_tpu.backend import JaxRoutedBackend
    from protocol_tpu.graph import barabasi_albert_edges
    from protocol_tpu.ops.routed import build_routed_operator

    n = 200
    src, dst, val = barabasi_albert_edges(n, 3, seed=5)
    valid = np.ones(n, dtype=bool)
    op = build_routed_operator(n, src, dst, val, valid)
    be = JaxRoutedBackend()
    for _ in range(2):
        be.converge_edges(n, src, dst, val, valid, 1000.0, 100, tol=1e-6,
                          alpha=0.1, operator=op)
    spans = trace.summary()
    for name in ("converge.stage", "converge.edges", "converge.fetch"):
        assert spans[name]["count"] == 2, name
    assert trace.counter_total("routed_stage", result="miss") == 1
    assert trace.counter_total("routed_stage", result="hit") == 1


def test_record_converge_stats_instruments(tracer):
    from protocol_tpu.ops.converge import record_converge_stats

    record_converge_stats("test-backend", 10, 1e-7, 2.0, n=100)
    assert trace.TRACER.gauge("converge_iterations").value(
        backend="test-backend", semiring="plusmul") == 10
    assert trace.TRACER.gauge("converge_residual").value(
        backend="test-backend", semiring="plusmul") == pytest.approx(1e-7)
    series = trace.TRACER.histogram("converge_sweep_seconds").series()
    assert series and series[0][1]["count"] == 1
    assert series[0][1]["sum"] == pytest.approx(0.2)  # 2.0s / 10 iters
    # fixed-iteration runs pass delta=None: iterations recorded,
    # residual untouched
    record_converge_stats("fixed-backend", 5, None, 1.0)
    assert trace.TRACER.gauge("converge_iterations").value(
        backend="fixed-backend", semiring="plusmul") == 5


def test_converge_edges_records_gauges_and_watch(tracer):
    np = pytest.importorskip("numpy")
    pytest.importorskip("jax")
    from protocol_tpu.backend import JaxSparseBackend
    from protocol_tpu.graph import barabasi_albert_edges

    n = 120
    src, dst, val = barabasi_albert_edges(n, 3, seed=3)
    scores, iters, delta = JaxSparseBackend().converge_edges(
        n, src, dst, val, np.ones(n, dtype=bool), 1000.0, 200, tol=1e-6)
    assert iters > 0
    assert trace.TRACER.gauge("converge_iterations").value(
        backend="jax-sparse", semiring="plusmul") == iters
    assert trace.TRACER.gauge("converge_residual").value(
        backend="jax-sparse", semiring="plusmul") == pytest.approx(delta)
    sweeps = trace.TRACER.histogram("converge_sweep_seconds").series()
    assert any(dict(items).get("backend") == "jax-sparse"
               for items, _ in sweeps)
    # rendering: the stage/converge families land on /metrics typed
    page = render_prometheus()
    assert "# TYPE ptpu_converge_sweep_seconds histogram" in page
    assert "ptpu_converge_iterations" in page
    assert lint_exposition(page) == []


def test_declared_instrument_families_render(tracer):
    from protocol_tpu.service.metrics import (
        HISTOGRAM_FAMILIES,
        declare_instruments,
    )

    declare_instruments()
    page = render_prometheus()
    for family in HISTOGRAM_FAMILIES:
        assert f"# TYPE ptpu_{family} histogram" in page, family
    assert "# TYPE ptpu_xla_compiles_total counter" in page
    assert "ptpu_xla_steady_recompiles_total 0" in page
    assert lint_exposition(page) == []


def test_prover_stage_histogram_renders(tracer):
    from protocol_tpu.zk.prover_fast import _stage

    with _stage("unit_stage", 7, "host"):
        pass
    page = render_prometheus()
    assert "# TYPE ptpu_prover_stage_seconds histogram" in page
    assert 'stage="unit_stage"' in page and 'path="host"' in page
    assert lint_exposition(page) == []


def test_device_trace_events_carry_trace_context(tracer, tmp_path):
    pytest.importorskip("jax")
    stream = tmp_path / "events.jsonl"
    trace.TRACER.disable()
    trace.TRACER.enable(str(stream))
    with trace.context(trace_id="prof-1"):
        with trace.device_trace(str(tmp_path / "xprof")):
            pass
    trace.TRACER.disable()
    trace.TRACER.enable()
    names = []
    with open(stream) as f:
        for line in f:
            obj = json.loads(line)
            if obj.get("type") == "event":
                names.append((obj["name"], obj.get("trace_id")))
    assert ("trace.device_trace_start", "prof-1") in names
    assert ("trace.device_trace_stop", "prof-1") in names
