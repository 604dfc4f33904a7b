"""Delta-engine tests (``protocol_tpu.incremental``): classification,
in-place patching, overflow tail, partial refresh, and — the load-
bearing property — equivalence with a from-scratch operator rebuild
under random mixed churn.

Tolerance notes: the engine and a fresh rebuild bucketize the SAME
normalized matrix differently (patched buffers + COO tail vs rebuilt
ELL), so their f32 reduction orders differ — per the PR 5 parity
diagnosis that shifts adaptive stopping by ±1 iteration at the
tolerance boundary and perturbs converged scores at the 1e-6-relative
level. Assertions compare against the converge tolerance, not bitwise.
"""

import numpy as np
import pytest

from protocol_tpu.backend import JaxRoutedBackend
from protocol_tpu.graph import barabasi_albert_edges, filter_edges
from protocol_tpu.incremental import DeltaEngine, partial_refresh
from protocol_tpu.ops.routed import build_routed_operator, spmv_routed

# 1e-5 rather than 1e-6: the engines converge in f32, whose relative-L1
# plateau on small graphs sits just above 1e-6 — the equivalence being
# tested is delta-vs-rebuild, not f32-vs-f64
TOL = 1e-5
MAX_IT = 200
INITIAL = 1000.0


def _edge_dict(n, src, dst, val):
    edges = {}
    for s, d, v in zip(src, dst, val):
        if s != d:
            edges[(int(s), int(d))] = edges.get((int(s), int(d)),
                                                0.0) + float(v)
    return edges


def _arrays(edges):
    src = np.array([k[0] for k in edges], dtype=np.int64)
    dst = np.array([k[1] for k in edges], dtype=np.int64)
    val = np.array([edges[k] for k in edges], dtype=np.float64)
    return src, dst, val


def _anchored(n=160, m=3, seed=1, **kw):
    src, dst, val = barabasi_albert_edges(n, m, seed=seed)
    valid = np.ones(n, dtype=bool)
    op = build_routed_operator(n, src, dst, val, valid)
    eng = DeltaEngine.anchor(n, src, dst, val, valid, op, **kw)
    return eng, _edge_dict(n, src, dst, val)


def _rebuild_scores(n, edges):
    src, dst, val = _arrays(edges)
    be = JaxRoutedBackend()
    return be.converge_edges(n, src, dst, val, np.ones(n, dtype=bool),
                             INITIAL, MAX_IT, tol=TOL)


def _rel_err(a, b):
    return float(np.max(np.abs(np.asarray(a) - np.asarray(b)))
                 / np.max(np.abs(b)))


# --- filter_edges raw view (the engine's index contract) --------------------


def test_filter_edges_return_raw_consistency():
    src, dst, val = barabasi_albert_edges(80, 3, seed=5)
    fsrc, fdst, w, valid, dangling, raw, row_sum = filter_edges(
        80, src, dst, val, return_raw=True)
    # the raw view normalizes to exactly the weights the short form
    # returns, in the same order
    np.testing.assert_allclose(raw / row_sum[fsrc], w)
    f2 = filter_edges(80, src, dst, val)
    np.testing.assert_array_equal(fsrc, f2[0])
    np.testing.assert_array_equal(fdst, f2[1])
    # deduped raw values re-sum to the per-row totals
    np.testing.assert_allclose(np.bincount(fsrc, weights=raw,
                                           minlength=80), row_sum)


# --- classification + patching ---------------------------------------------


def test_delta_classification_kinds():
    eng, edges = _anchored()
    (i, j) = next(iter(edges))
    missing = next((a, b) for a in range(160) for b in range(160)
                   if a != b and (a, b) not in edges)
    deltas = [
        (i, j, edges[(i, j)], 42.0),              # weight revision
        (missing[0], missing[1], None, 3.0),      # structural insert
    ]
    assert eng.apply_deltas(deltas)
    assert eng.stats.revisions == 1
    assert eng.stats.inserts == 1
    assert len(eng.tail_index) == 1
    assert eng.tail_live == 1
    # removal of the tail edge zeroes it in place; removal of a
    # never-present edge is a no-op
    assert eng.apply_deltas([
        (missing[0], missing[1], 3.0, 0.0),
        (5, 7, None, 0.0) if (5, 7) not in edges else (i, j, 42.0, 42.0),
    ])
    assert eng.tail_live == 0
    assert eng.stats.removes >= 1
    # revival reuses the tail slot instead of appending
    assert eng.apply_deltas([(missing[0], missing[1], 0.0, 9.0)])
    assert len(eng.tail_index) == 1 and eng.tail_live == 1


def test_delta_new_peer_gets_free_state_slot():
    eng, edges = _anchored()
    n0 = eng.n_now
    assert eng.apply_deltas([(n0, 0, None, 5.0)], n=n0 + 1)
    assert eng.n_now == n0 + 1
    assert eng.n_valid == n0 + 1
    slot = eng.node_to_state[n0]
    assert slot >= 0 and eng.state_to_node[slot] == n0
    assert eng.valid_state[slot] == 1.0
    assert eng.stats.new_peers == 1
    # peers interned without any edge delta still grow the engine
    assert eng.apply_deltas([], n=n0 + 3)
    assert eng.n_now == n0 + 3
    assert bool(eng.dangling_np[n0 + 2])  # no out-edges yet


def test_delta_tail_capacity_wall_forces_rebuild():
    eng, edges = _anchored(tail_min_capacity=4, tail_max=3)
    fresh = [(a, b) for a in range(160) for b in range(160)
             if a != b and (a, b) not in edges][:4]
    deltas = [(a, b, None, 2.0) for a, b in fresh]
    assert not eng.apply_deltas(deltas)
    assert eng.stats.rebuild_reason == "tail_max"
    # a dead engine stays dead (the caller re-anchors)
    assert not eng.apply_deltas([])


def test_delta_state_slot_exhaustion_forces_rebuild():
    eng, _ = _anchored()
    headroom = len(eng.free_slots) - eng._free_ptr
    assert not eng.apply_deltas([], n=eng.n_now + headroom + 1)
    assert eng.stats.rebuild_reason == "state_slots_exhausted"


# --- patched matvec equivalence --------------------------------------------


def test_patched_spmv_matches_rebuilt_operator():
    """ONE application of the patched operator (inv_row_scale + tail
    fold-in) must match one application of a from-scratch rebuild —
    sweep-level equivalence, no convergence slack to hide behind."""
    import jax.numpy as jnp

    eng, edges = _anchored(n=96, m=2, seed=3)
    rng = np.random.default_rng(0)
    keys = list(edges)
    deltas = []
    for k in rng.choice(len(keys), 12, replace=False):
        i, j = keys[k]
        new = float(rng.integers(1, 30))
        deltas.append((i, j, edges[(i, j)], new))
        edges[(i, j)] = new
    missing = [(a, b) for a in range(96) for b in range(96)
               if a != b and (a, b) not in edges][:5]
    for a, b in missing:
        deltas.append((a, b, None, 4.0))
        edges[(a, b)] = 4.0
    assert eng.apply_deltas(deltas)

    src, dst, val = _arrays(edges)
    op2 = build_routed_operator(96, src, dst, val,
                                np.ones(96, dtype=bool))
    from protocol_tpu.ops.routed import routed_arrays

    arrs2, static2 = routed_arrays(op2)
    s_node = rng.uniform(0.5, 2.0, size=96)
    y1 = eng.scores_to_nodes(np.asarray(spmv_routed(
        eng.arrs, eng.static, jnp.asarray(eng.scores_to_state(s_node)))))
    y2 = op2.scores_for_nodes(np.asarray(spmv_routed(
        arrs2, static2,
        jnp.asarray(op2.scores_from_nodes(s_node)))))
    np.testing.assert_allclose(y1, y2, rtol=2e-5, atol=1e-7)


# --- the property test: random mixed churn vs rebuild -----------------------


def test_delta_engine_matches_rebuild_under_mixed_churn():
    rng = np.random.default_rng(11)
    n = 150
    eng, edges = _anchored(n=n, m=3, seed=7)
    n_now = n
    for round_ in range(4):
        deltas = []
        keys = [k for k in edges if edges[k] > 0]
        # revisions
        for k in rng.choice(len(keys), 10, replace=False):
            i, j = keys[k]
            new = float(rng.integers(1, 25))
            deltas.append((i, j, edges[(i, j)], new))
            edges[(i, j)] = new
        # inserts
        added = 0
        while added < 3:
            a, b = int(rng.integers(0, n_now)), int(rng.integers(0, n_now))
            if a == b or edges.get((a, b), 0.0) > 0:
                continue
            old = edges.get((a, b))
            edges[(a, b)] = 6.0
            deltas.append((a, b, old, 6.0))
            added += 1
        # removals
        for k in rng.choice(len(keys), 3, replace=False):
            i, j = keys[k]
            if edges[(i, j)] <= 0:
                continue
            deltas.append((i, j, edges[(i, j)], 0.0))
            edges[(i, j)] = 0.0
        # occasionally, a brand-new peer
        if round_ % 2 == 0:
            edges[(n_now, 0)] = 2.0
            deltas.append((n_now, 0, None, 2.0))
            n_now += 1
        assert eng.apply_deltas(deltas, n=n_now), eng.stats
        s_eng, it_e, d_e = eng.converge(
            eng.initial_node_scores(INITIAL), MAX_IT, TOL)
        s_ref, it_r, d_r = _rebuild_scores(n_now, edges)
        assert _rel_err(s_eng, s_ref) < 1e-4, \
            f"round {round_}: delta scores diverged"
        assert d_e <= TOL and d_r <= TOL
        # reduction-order slack only (PR 5 diagnosis)
        assert abs(int(it_e) - int(it_r)) <= 2, \
            f"round {round_}: iterations {it_e} vs {it_r}"


def test_engine_patches_stay_off_the_operators_staging():
    """The engine anchors on the operator's resident staging and patches
    only its own dict: after a revision batch a converge on the operator
    still returns the pre-patch scores."""
    from protocol_tpu.utils import trace

    trace.enable()
    misses = trace.counter_total("routed_stage", result="miss")
    hits = trace.counter_total("routed_stage", result="hit")
    n = 160
    src, dst, val = barabasi_albert_edges(n, 3, seed=1)
    valid = np.ones(n, dtype=bool)
    op = build_routed_operator(n, src, dst, val, valid)

    def converge():
        return JaxRoutedBackend().converge_edges(
            n, src, dst, val, valid, INITIAL, MAX_IT, tol=TOL, operator=op)

    before = converge()
    eng = DeltaEngine.anchor(n, src, dst, val, valid, op)
    edges = _edge_dict(n, src, dst, val)
    assert eng.apply_deltas([(i, j, v, 3.0 * v + 1.0) for (i, j), v
                             in list(edges.items())[:10]], n=n), eng.stats
    patched = eng.converge(eng.initial_node_scores(INITIAL), MAX_IT, TOL)
    after = converge()
    assert np.array_equal(after[0], before[0]) and after[1:] == before[1:]
    assert not np.allclose(patched[0], before[0])
    assert trace.counter_total("routed_stage", result="miss") == misses + 1
    assert trace.counter_total("routed_stage", result="hit") == hits + 2


# --- partial refresh ---------------------------------------------------------


def test_partial_refresh_residual_parity_with_full_sweep():
    rng = np.random.default_rng(5)
    n = 300
    eng, edges = _anchored(n=n, m=3, seed=9)
    s_pub, it0, d0 = eng.converge(eng.initial_node_scores(INITIAL),
                                  500, TOL)
    assert d0 <= TOL
    eng.take_frontier()
    keys = list(edges)
    for k in rng.choice(len(keys), 5, replace=False):
        i, j = keys[k]
        new = edges[(i, j)] * 1.7
        assert eng.apply_deltas([(i, j, edges[(i, j)], new)])
        edges[(i, j)] = new
    frontier, partial_ok = eng.take_frontier()
    assert partial_ok and len(frontier)
    assert isinstance(frontier, np.ndarray)  # no per-element int() loop
    res = partial_refresh(eng, s_pub, frontier, TOL, 500,
                          frontier_limit=n)
    assert res is not None, "partial refresh fell back unexpectedly"
    # residual parity: the partial sweeps reach the same stopping bound
    # the full-sweep twin reaches from the same warm vector
    assert res.residual <= TOL
    s_full, it_f, d_f = eng.converge(s_pub, 500, TOL)
    assert d_f <= TOL
    # score parity is tolerance-semantics, not bitwise: both stop when
    # the per-sweep delta ≤ tol, and with a per-sweep contraction rate
    # r the remaining distance to the fixed point is up to tol/(1−r) —
    # a few×1e-3 relative on this slowly-mixing graph. The bound below
    # is that stopping-window width, not numerical noise.
    assert _rel_err(res.scores, s_full) < 5e-3
    s_ref, _, _ = _rebuild_scores(n, edges)
    assert _rel_err(res.scores, s_ref) < 5e-3


def test_partial_refresh_declines_without_footing():
    eng, edges = _anchored(n=96, m=2, seed=13)
    s_pub, _, _ = eng.converge(eng.initial_node_scores(INITIAL),
                               MAX_IT, TOL)
    eng.take_frontier()
    # a new peer voids partial footing (n_valid changed)
    assert eng.apply_deltas([(96, 0, None, 3.0)], n=97)
    frontier, partial_ok = eng.take_frontier()
    assert not partial_ok
    # frontier bound: a tiny limit forces the full-sweep fallback
    (i, j) = next(k for k in edges if edges[k] > 0)
    assert eng.apply_deltas([(i, j, edges[(i, j)],
                              edges[(i, j)] + 1.0)])
    frontier, partial_ok = eng.take_frontier()
    assert partial_ok
    s_pub2 = np.concatenate([s_pub, [INITIAL]])
    assert partial_refresh(eng, s_pub2, frontier, TOL, 500,
                           frontier_limit=0) is None
    # restore_frontier puts a drained frontier back for the retry
    eng.restore_frontier(frontier, partial_ok)
    f2, ok2 = eng.take_frontier()
    assert np.array_equal(f2, np.unique(frontier)) and ok2


def test_tail_fanin_index_stays_o_dirty_at_large_tail():
    """Satellite regression (ROADMAP item 1 follow-up): with ~2·10⁴
    overflow-tail edges, a churn batch's partial refresh must examine
    only the tail edges ADJACENT to the frontier — the pre-index
    linear scan re-read the whole tail per sweep, dominating batches
    past ~10⁴ tail edges. Phase 1: churn far from the tail block →
    near-zero tail entries visited. Phase 2: churn ON a tail edge →
    the indexed traversal still beats the per-sweep full scan by ≥5×
    while matching the full-sweep scores."""
    rng = np.random.default_rng(3)
    n = 5000
    ids = np.arange(n)
    # two out-edges per node (weights 2:1) so revisions genuinely move
    # the normalized operator, ring-shaped so churn at node 0 stays
    # topologically far from the tail block below
    src = np.concatenate([ids, ids])
    dst = np.concatenate([(ids + 1) % n, (ids + 2) % n])
    val = np.concatenate([np.full(n, 2.0), np.full(n, 1.0)])
    valid = np.ones(n, dtype=bool)
    op = build_routed_operator(n, src, dst, val, valid)
    # alpha: the near-periodic ring mixes too slowly for the f32
    # adaptive loop at this size — damping restores geometric
    # convergence without changing what the index test measures
    eng = DeltaEngine.anchor(n, src, dst, val, valid, op, alpha=0.15,
                             tail_max=1 << 17, tail_fraction=100.0)
    # structural inserts confined to the block [1000, 4000) → a tail
    # big enough that a linear scan would dominate the batch. The edge
    # map supplies TRUE old values (the engine's caller contract — the
    # service's edge-change log does the same): a random pair can
    # collide with a built ring edge or an earlier insert, and a wrong
    # old corrupts the telescoped row sums (mass leak).
    edges = _edge_dict(n, src, dst, val)
    lo, hi = 1000, 4000
    ts = rng.integers(lo, hi, 24_000)
    td = rng.integers(lo, hi, 24_000)
    inserts = []
    for a, b in zip(ts, td):
        a, b = int(a), int(b)
        if a == b:
            continue
        old = edges.get((a, b), 0.0)
        new = float(rng.integers(1, 9))
        inserts.append((a, b, old if old > 0 else None, new))
        edges[(a, b)] = new
    assert eng.apply_deltas(inserts)
    tail = len(eng.tail_index)
    assert tail >= 10_000, f"tail too small to regress on ({tail})"
    s_pub, _, d0 = eng.converge(eng.initial_node_scores(INITIAL),
                                MAX_IT, TOL)
    assert d0 <= TOL
    eng.take_frontier()

    # --- phase 1: churn far from the tail ---------------------------
    eng.tail_fanin_visited = eng.tail_fanout_visited = 0
    assert eng.apply_deltas([(i, (i + 1) % n, 2.0, 5.0)
                             for i in range(5)])
    frontier, ok = eng.take_frontier()
    assert ok
    res = partial_refresh(eng, s_pub, frontier, TOL, 500,
                          frontier_limit=n)
    assert res is not None, "partial refresh fell back unexpectedly"
    visited = eng.tail_fanin_visited + eng.tail_fanout_visited
    # the scan this replaces examined the WHOLE tail once per sweep
    assert visited < tail / 10, \
        f"visited {visited} tail entries of {tail} (O(tail) scan?)"
    s_full, _, _ = eng.converge(s_pub, MAX_IT, TOL)
    assert _rel_err(res.scores, s_full) < 5e-3

    # --- phase 2: churn ON a tail edge ------------------------------
    eng.take_frontier()
    eng.tail_fanin_visited = eng.tail_fanout_visited = 0
    t0 = int(np.argmax(eng.tail_raw_np > 0))
    a, b = int(eng.tail_src_np[t0]), int(eng.tail_dst_np[t0])
    old = float(eng.tail_raw_np[t0])
    assert eng.apply_deltas([(a, b, old, old + 3.0)])
    frontier, ok = eng.take_frontier()
    assert ok and b in frontier
    res2 = partial_refresh(eng, s_full, frontier, TOL, 500,
                           frontier_limit=n)
    assert res2 is not None
    # the frontier legitimately floods the dense tail block here, so
    # the sharp O(dirty) bound is phase 1's; this phase proves the
    # indexed fan-in path is EXERCISED and correct under tail traffic
    assert eng.tail_fanin_visited > 0
    s_full2, _, _ = eng.converge(s_full, MAX_IT, TOL)
    assert _rel_err(res2.scores, s_full2) < 5e-3


# --- refresher integration ---------------------------------------------------


class _FakeSigned:
    def __init__(self, about, value):
        self.attestation = type("A", (), {"about": about,
                                          "value": value})()


def _counter_total(name):
    from protocol_tpu.utils import trace

    for inst in trace.TRACER.instruments():
        if inst.name == name and inst.kind == "counter":
            return sum(v for _, v in inst.samples())
    return 0.0


def test_refresher_absorbs_revision_churn_without_builds():
    from protocol_tpu.service.config import ServiceConfig
    from protocol_tpu.service.refresh import ScoreRefresher
    from protocol_tpu.service.state import OpinionGraph
    from protocol_tpu.utils import trace

    trace.enable()
    g = OpinionGraph()
    cfg = ServiceConfig(routed_edge_threshold=1, tol=1e-8)
    r = ScoreRefresher(g, cfg)
    a = [bytes([i + 1]) * 20 for i in range(4)]
    g.apply([_FakeSigned(a[1], 7), _FakeSigned(a[2], 3)], [a[0], a[0]])
    g.apply([_FakeSigned(a[0], 9), _FakeSigned(a[3], 2)], [a[1], a[2]])
    r.refresh()
    assert r.delta_engine is not None, "routed refresh must anchor"
    builds0 = _counter_total("operator_full_builds")
    for k in range(3):
        g.apply([_FakeSigned(a[1], 10 + k)], [a[0]])
        t = r.refresh()
        assert t.revision == g.revision
    assert _counter_total("operator_full_builds") == builds0, \
        "revision churn paid a full plan build"
    assert r.delta_batches == 3
    # scores still match a from-scratch rebuild of the same graph
    n, src, dst, val, _, _ = g.snapshot()
    s_ref, _, _ = JaxRoutedBackend().converge_edges(
        n, src, dst, val, np.ones(n, dtype=bool), cfg.initial_score,
        cfg.max_iterations, tol=cfg.tol)
    np.testing.assert_allclose(r.table.scores, s_ref, rtol=1e-3)


def test_refresher_reanchors_on_lost_delta_log():
    from protocol_tpu.service.config import ServiceConfig
    from protocol_tpu.service.refresh import ScoreRefresher
    from protocol_tpu.service.state import OpinionGraph
    from protocol_tpu.utils import trace

    trace.enable()
    g = OpinionGraph()
    cfg = ServiceConfig(routed_edge_threshold=1, tol=1e-8)
    r = ScoreRefresher(g, cfg)
    a = [bytes([i + 1]) * 20 for i in range(2)]
    g.apply([_FakeSigned(a[1], 7)], [a[0]])
    g.apply([_FakeSigned(a[0], 9)], [a[1]])
    r.refresh()
    assert r.delta_engine is not None
    g.apply([_FakeSigned(a[1], 3)], [a[0]])
    g._delta_lost = True  # simulate log overflow
    r.refresh()
    assert r.delta_reanchors == 1
    # the rebuild path re-anchored a fresh engine
    assert r.delta_engine is not None


def test_opinion_graph_delta_log_drains_atomically():
    from protocol_tpu.service.state import OpinionGraph

    g = OpinionGraph()
    a = [bytes([i + 1]) * 20 for i in range(2)]
    g.apply([_FakeSigned(a[1], 7)], [a[0]])
    g.apply([_FakeSigned(a[1], 9)], [a[0]])   # revision
    g.apply([_FakeSigned(a[1], 9)], [a[0]])   # no-op: same value
    out = g.snapshot(drain_deltas=True)
    assert len(out) == 8
    deltas, lost = out[6], out[7]
    assert not lost
    assert deltas == [(0, 1, None, 7.0), (0, 1, 7.0, 9.0)]
    # drained: a second snapshot sees nothing
    assert g.snapshot(drain_deltas=True)[6] == []
    # plain snapshot keeps the legacy shape
    assert len(g.snapshot()) == 6


def test_ensure_edge_slots_respects_build_min_width():
    """Upgrading a cached pre-delta operator must re-derive slots under
    the min_width THE BUILD USED (persisted on the operator) — a
    hardcoded default would compute addresses for the wrong bucket
    geometry and silently scatter patches into the wrong (row, lane)
    positions."""
    from protocol_tpu.ops.routed import ensure_edge_slots

    n, m = 160, 3
    src, dst, val = barabasi_albert_edges(n, m, seed=4)
    valid = np.ones(n, dtype=bool)
    op = build_routed_operator(n, src, dst, val, valid, min_width=32)
    assert op.min_width == 32
    built_slots = op.out_edge_slot.copy()
    op.out_edge_slot = None  # simulate a cache from before the field
    fsrc, fdst, fweight, _, _ = filter_edges(n, src, dst, val, valid)
    ensure_edge_slots(op, fsrc, fdst, fweight)
    np.testing.assert_array_equal(op.out_edge_slot, built_slots)

    # and the engine end-to-end on the non-default geometry: revisions
    # patched through those slots still match a from-scratch rebuild
    eng = DeltaEngine.anchor(n, src, dst, val, valid, op)
    s0 = eng.converge(eng.initial_node_scores(INITIAL), MAX_IT, TOL)[0]
    eng.take_frontier()
    edges = _edge_dict(n, src, dst, val)
    rng = np.random.default_rng(9)
    keys = list(edges)
    deltas = []
    for k in rng.choice(len(keys), 40, replace=False):
        key = keys[k]
        new = float(rng.integers(1, 11))
        deltas.append((key[0], key[1], edges[key], new))
        edges[key] = new
    assert eng.apply_deltas(deltas)
    got = eng.converge(s0, MAX_IT, TOL)[0]
    ref, _, _ = _rebuild_scores(n, edges)
    assert _rel_err(got, ref) <= 10 * TOL


def test_refresher_partial_refresh_on_localized_churn():
    """At the ScoreRefresher level (not just the engine): a warm
    refresh over a LOCALIZED churn window on a big-enough graph must
    be served by partial sweeps — the dirty frontier stays under the
    budget — and still publish rebuild-accurate scores."""
    from protocol_tpu.service.config import ServiceConfig
    from protocol_tpu.service.refresh import ScoreRefresher
    from protocol_tpu.service.state import OpinionGraph
    from protocol_tpu.utils import trace

    trace.enable()
    g = OpinionGraph()
    cfg = ServiceConfig(routed_edge_threshold=1, tol=1e-8,
                        partial_frontier_fraction=1.0,
                        cold_edit_fraction=1e9, cold_every=0)
    r = ScoreRefresher(g, cfg)
    n = 40
    a = [bytes([i + 1]) * 20 for i in range(n)]
    src, dst, val = barabasi_albert_edges(n, 3, seed=6)
    for s, d, v in zip(src, dst, val):
        if s != d:
            g.apply([_FakeSigned(a[int(d)], float(v))], [a[int(s)]])
    r.refresh()
    assert r.delta_engine is not None, "routed refresh must anchor"
    builds0 = _counter_total("operator_full_builds")
    # one existing edge revised per window: frontier = its fan-out
    s0, d0 = int(src[0]), int(dst[0])
    for k in range(2):
        g.apply([_FakeSigned(a[d0], 20.0 + k)], [a[s0]])
        t = r.refresh()
        assert t.revision == g.revision
    assert r.partial_refreshes >= 1, \
        f"localized churn never took the partial path ({r.delta_status()})"
    assert _counter_total("operator_full_builds") == builds0
    gn, gsrc, gdst, gval, _, _ = g.snapshot()
    s_ref, _, _ = JaxRoutedBackend().converge_edges(
        gn, gsrc, gdst, gval, np.ones(gn, dtype=bool),
        cfg.initial_score, cfg.max_iterations, tol=cfg.tol)
    np.testing.assert_allclose(r.table.scores, s_ref, rtol=1e-3)
