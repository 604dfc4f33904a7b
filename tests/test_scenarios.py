"""Scenario harness + semiring seam tests (tier-1).

Three layers, matching the subsystem's pillars:

- the semiring seam: the pluggable (+,×) path must be byte-identical
  to the pre-existing kernels (same iterates, ±0 iterations), and the
  ``maxplus`` variant must match a brute-force dense widest-path
  (bottleneck) oracle on random graphs;
- topic batching: K vmapped topic vectors through ONE operator must
  equal K independent converges, with exactly one routing-plan build;
- the adversarial generators, robustness metrics, and the runner's
  byte-identical-per-seed reproducibility contract.

Everything runs on the CPU backend at small scale; the large-scale
numbers live in BENCH_r12.
"""

import json

import numpy as np
import pytest

import jax.numpy as jnp

from protocol_tpu.backend import JaxRoutedBackend, JaxSparseBackend
from protocol_tpu.graph import barabasi_albert_edges, filter_edges
from protocol_tpu.ops.converge import (
    MAXPLUS,
    PLUSMUL,
    converge_sparse_adaptive,
    converge_sparse_adaptive_semiring,
    operator_arrays,
    resolve_semiring,
)
from protocol_tpu.scenarios import (
    TOPOLOGIES,
    build_topology,
    list_scenarios,
    run_scenario,
)
from protocol_tpu.scenarios.metrics import (
    attacker_mass_capture,
    attackers_in_top,
    iteration_bound,
    rank_displacement,
)
from protocol_tpu.utils import trace


def random_edges(n, n_edges, seed):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, n_edges)
    dst = rng.integers(0, n, n_edges)
    val = rng.integers(1, 10, n_edges).astype(np.float64)
    keep = src != dst
    return src[keep], dst[keep], val[keep]


# --- semiring seam ------------------------------------------------------


def maxplus_dense_oracle(n, src, dst, val, s0, max_iters=200):
    """Brute-force widest-path fixpoint on the dense normalized matrix:
    ``s[i] = max_j min(W[j, i], s[j])`` iterated until unchanged,
    invalid peers masked to 0. Weights go through the SAME
    filter/normalize front door as the operator path, then get the
    same float32 cast ``operator_arrays`` applies — max/min only ever
    SELECT among those values, so the oracle and the bucketed kernel
    agree exactly, not just approximately."""
    fsrc, fdst, w, valid, _ = filter_edges(n, src, dst, val)
    W = np.zeros((n, n), dtype=np.float32)
    W[fsrc, fdst] = w.astype(np.float32)
    vmask = valid.astype(np.float32)
    s = np.asarray(s0, dtype=np.float32)
    for _ in range(max_iters):
        s2 = np.max(np.minimum(W, s[:, None]), axis=0) * vmask
        if np.array_equal(s2, s):
            break
        s = s2
    return s


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_maxplus_matches_widest_path_oracle(seed):
    rng = np.random.default_rng(100 + seed)
    n = int(rng.integers(8, 65))
    src, dst, val = random_edges(n, 4 * n, 200 + seed)
    s0 = (np.ones(n) * 1000.0).astype(np.float32)
    scores, iters, delta = JaxSparseBackend().converge_edges(
        n, src, dst, val, None, 1000.0, 200, tol=1e-12,
        semiring="maxplus")
    oracle = maxplus_dense_oracle(n, src, dst, val, s0)
    # the fixed point is reached exactly (max-min is a selection),
    # so the comparison is equality, not tolerance
    assert np.array_equal(np.asarray(scores, dtype=np.float32), oracle)
    assert delta == 0.0
    assert iters <= 200


def test_maxplus_semantics_pinned_small_graph():
    """Hand-checked bottleneck fixpoint on a 3-node graph with a
    sustaining cycle 0↔1 (normalized weights: 0→1 is 1.0; 1→0 is
    0.75, 1→2 is 0.25). The fixed point is the best CYCLE-sustained
    bottleneck into each node: s[0] = min(w(1→0), s[1]) = 0.75,
    s[1] = min(w(0→1), s[0]) = 0.75, s[2] = min(w(1→2), s[1]) = 0.25.
    A score not fed by a cycle decays to 0 — path semantics, no mass
    conservation (the semiring docstring's contract)."""
    src = np.array([0, 1, 1])
    dst = np.array([1, 0, 2])
    val = np.array([1.0, 3.0, 1.0])
    scores, iters, delta = JaxSparseBackend().converge_edges(
        3, src, dst, val, None, 1000.0, 50, tol=1e-12,
        semiring=MAXPLUS)
    np.testing.assert_allclose(
        np.asarray(scores), [0.75, 0.75, 0.25], atol=1e-6)
    assert delta == 0.0
    # and the decay contract: a pure chain (no cycle) fixes at 0
    chain = JaxSparseBackend().converge_edges(
        3, np.array([0, 1]), np.array([1, 2]), np.array([2.0, 2.0]),
        None, 1000.0, 50, semiring=MAXPLUS)
    np.testing.assert_allclose(np.asarray(chain), [0.0, 0.0, 0.0])


def test_default_semiring_trajectory_byte_identical():
    """The (+,×) algebra through the GENERALIZED semiring path must
    reproduce the pre-existing kernel's iterate trajectory exactly —
    same scores bit-for-bit, same iteration count (±0). This pins the
    refactor's no-op contract for the default path."""
    from protocol_tpu.graph import build_operator

    n = 300
    src, dst, val = barabasi_albert_edges(n, 4, seed=9)
    op = build_operator(n, src, dst, val, None)
    arrs = operator_arrays(op, dtype=jnp.float32, alpha=0.1)
    s0 = jnp.asarray(op.valid, dtype=jnp.float32) * 1000.0
    ref_s, ref_iters, ref_delta = converge_sparse_adaptive(
        arrs, s0, tol=1e-6, max_iterations=100)
    gen_s, gen_iters, gen_delta = converge_sparse_adaptive_semiring(
        arrs, s0, PLUSMUL, tol=1e-6, max_iterations=100)
    assert int(ref_iters) == int(gen_iters)
    assert np.array_equal(np.asarray(ref_s), np.asarray(gen_s))
    assert float(ref_delta) == float(gen_delta)


def test_backend_default_path_ignores_semiring_seam():
    """``semiring=None`` and ``semiring="plusmul"`` both route through
    the pre-existing kernels — identical outputs, identical iteration
    counts."""
    n = 200
    src, dst, val = barabasi_albert_edges(n, 3, seed=4)
    be = JaxSparseBackend()
    a, ia, da = be.converge_edges(n, src, dst, val, None, 1000.0, 100,
                                  tol=1e-6, alpha=0.1)
    b, ib, db = be.converge_edges(n, src, dst, val, None, 1000.0, 100,
                                  tol=1e-6, alpha=0.1,
                                  semiring="plusmul")
    assert ia == ib and da == db
    assert np.array_equal(a, b)


def test_resolve_semiring_validation():
    assert resolve_semiring(None) is PLUSMUL
    assert resolve_semiring("maxplus") is MAXPLUS
    assert resolve_semiring(MAXPLUS) is MAXPLUS
    with pytest.raises(ValueError, match="unknown semiring"):
        resolve_semiring("minplus")


# --- topic batching -----------------------------------------------------


@pytest.fixture()
def tracer():
    trace.TRACER.reset()
    trace.TRACER.reset_instruments()
    was_enabled = trace.TRACER.enabled
    trace.TRACER.enable()
    yield trace.TRACER
    trace.TRACER.reset()
    trace.TRACER.reset_instruments()
    if not was_enabled:
        trace.TRACER.disable()


def _hist_count(name):
    return sum(s["count"]
               for _, s in trace.TRACER.histogram(name).series())


def test_topic_batch_matches_independent_converges(tracer):
    """K vmapped topic vectors through ONE routed operator == K
    independent converges (to 1e-12 relative), with exactly ONE
    routing-plan build paid for all K topics. Both sides run in float64:
    the backends default to float32, whose last-ulp differences between
    the vmapped and the single-vector programs the 1e-12 bound would
    read as divergence."""
    n, k = 400, 5
    src, dst, val = barabasi_albert_edges(n, 4, seed=11)
    rng = np.random.default_rng(11)
    s0k = rng.uniform(0.5, 1.5, (k, n)) * 1000.0

    seq = []
    for topic in range(k):
        s, iters, _ = JaxRoutedBackend(dtype=np.float64).converge_edges(
            n, src, dst, val, None, 1000.0, 100, tol=1e-6, alpha=0.1,
            s0=s0k[topic])
        seq.append((s, int(iters)))
    builds_before = _hist_count("routed_plan_build_seconds")

    scores, iters, delta = JaxRoutedBackend(dtype=np.float64).converge_topics(
        n, src, dst, val, None, s0k, 100, tol=1e-6, alpha=0.1)
    builds_after = _hist_count("routed_plan_build_seconds")
    assert builds_after - builds_before == 1, \
        "topic batch must pay exactly one routing-plan build"

    assert scores.shape == (k, n)
    for topic in range(k):
        ref, ref_iters = seq[topic]
        rel = np.max(np.abs(scores[topic] - ref)) / 1000.0
        assert rel <= 1e-12, f"topic {topic}: rel err {rel}"
        assert int(iters[topic]) == ref_iters, \
            "vmapped trajectory diverged from the independent converge"


def test_topic_batch_validates_shape():
    with pytest.raises(ValueError, match=r"s0_topics must be"):
        JaxSparseBackend().converge_topics(
            10, np.array([0]), np.array([1]), np.array([1.0]), None,
            np.ones(10), 10)


# --- adversarial generators ---------------------------------------------


@pytest.mark.parametrize("name", sorted(TOPOLOGIES))
def test_topology_deterministic_and_well_formed(name):
    kwargs = {"peers": 800, "seed": 13}
    if name != "smallworld":
        kwargs["attacker_fraction"] = 0.1
    g1 = build_topology(name, **kwargs)
    g2 = build_topology(name, **kwargs)
    for field in ("src", "dst", "val", "attacker"):
        assert np.array_equal(getattr(g1, field), getattr(g2, field)), \
            f"{name}.{field} not deterministic under a fixed seed"
    g3 = build_topology(name, **{**kwargs, "seed": 14})
    assert not (np.array_equal(g1.src, g3.src)
                and np.array_equal(g1.dst, g3.dst)), \
        f"{name} ignores its seed"
    assert g1.n == 800
    assert g1.src.shape == g1.dst.shape == g1.val.shape
    # self-edges are allowed in the raw arrays (filter_edges drops
    # them — the sybil funnel deliberately emits one), but they must
    # stay incidental, not a structural fraction of the graph
    assert (g1.src == g1.dst).mean() < 0.02, \
        f"{name} emitted a structural fraction of self-edges"
    assert (0 <= g1.src).all() and (g1.src < g1.n).all()
    assert (0 <= g1.dst).all() and (g1.dst < g1.n).all()
    assert (g1.val > 0).all()
    assert int(g1.attacker.sum()) == g1.n_attackers
    if name == "smallworld":
        assert g1.n_attackers == 0
    else:
        assert g1.n_attackers == int(800 * 0.1)


def test_unknown_topology_rejected():
    with pytest.raises(ValueError, match="unknown topology"):
        build_topology("star", peers=10)


# --- robustness metrics -------------------------------------------------


def test_attacker_mass_capture():
    scores = np.array([1.0, 2.0, 3.0, 4.0])
    attacker = np.array([False, True, False, True])
    assert attacker_mass_capture(scores, attacker) == pytest.approx(0.6)
    assert attacker_mass_capture(np.zeros(4), attacker) == 0.0


def test_rank_displacement_counts_honest_reorderings():
    # honest peers 0..3 hold ranks (by descending score); peer 1 and 2
    # swap between baseline and attacked
    base = np.array([9.0, 5.0, 4.0, 1.0, 100.0])
    att = np.array([9.0, 4.0, 5.0, 1.0, 100.0])
    honest = np.array([True, True, True, True, False])
    d = rank_displacement(base, att, honest)
    assert d["max"] == 1
    assert d["moved_fraction"] == pytest.approx(0.5)
    assert d["mean"] == pytest.approx(0.5)
    same = rank_displacement(base, base, honest)
    assert same["max"] == 0 and same["moved_fraction"] == 0.0
    with pytest.raises(ValueError):
        rank_displacement(base, att[:-1], honest)


def test_attackers_in_top():
    scores = np.array([10.0, 9.0, 8.0, 1.0])
    attacker = np.array([True, False, True, False])
    assert attackers_in_top(scores, attacker, top=2) == 1
    assert attackers_in_top(scores, attacker, top=3) == 2


def test_iteration_bound():
    # ceil(ln(1e-6) / ln(0.9)) = 132: the damped-convergence prediction
    assert iteration_bound(0.1, 1e-6) == 132
    assert iteration_bound(0.0, 1e-6) is None
    assert iteration_bound(1.0, 1e-6) is None


# --- the runner ---------------------------------------------------------


def test_run_scenario_reproducible_and_within_bound():
    kwargs = dict(topology="sybil-ring", peers=600,
                  attacker_fraction=0.1, seed=5, alpha=0.1)
    r1 = run_scenario(**kwargs)
    r2 = run_scenario(**kwargs)
    assert json.dumps(r1, sort_keys=True) == json.dumps(r2, sort_keys=True), \
        "same seed must reproduce the report byte-identically"
    assert r1["schema"] == "ptpu-scenario-v1"
    assert "timing_s" not in r1, \
        "timing is opt-in (it breaks byte-identical reproducibility)"
    rb = r1["robustness"]
    assert rb["within_bound"] is True
    assert rb["iterations"] <= rb["iteration_bound"] == 132
    # the ring must capture MORE mass than the attacker-free baseline
    assert rb["attacker_mass_capture"] > rb["baseline_attacker_mass"]


def test_run_scenario_maxplus_and_timing():
    r = run_scenario(topology="collusion", peers=400, seed=3,
                     semiring="maxplus", timing=True)
    assert r["semiring"] == "maxplus"
    assert set(r["timing_s"]) >= {"build", "attack_converge"}
    # no damping bound under path semantics? alpha is still recorded,
    # and the report stays well-formed either way
    assert r["robustness"]["attacker_mass_capture"] >= 0.0


def test_list_scenarios_catalog():
    cat = list_scenarios()
    names = {c["topology"] for c in cat}
    assert names == set(TOPOLOGIES)
    for c in cat:
        assert c["description"]
        assert "peers" in c["defaults"]
        assert "seed" in c["defaults"]
