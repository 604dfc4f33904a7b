"""EigenTrust convergence kernels — the TPU side of the ConvergeBackend seam.

The reference's hot loop (``dynamic_sets/native.rs:319-329``) is a dense
O(I·N²) nested Python-style loop in the BN254 field; here the real-valued
twin runs as:

- **dense**: ``s ← s @ C`` under ``lax.fori_loop`` / ``lax.while_loop`` —
  an MXU matvec per iteration; right choice for fully-connected sets up to
  a few thousand peers.
- **sparse**: gather-SpMV over the degree-bucketed ELL transpose built by
  ``protocol_tpu.graph.build_operator`` — pure gathers + row reductions
  (VPU-friendly, no scatters), with the dangling-mass rank-1 correction
  applied implicitly.

Both come in fixed-iteration form (reference parity: exactly
NUM_ITERATIONS steps, ``circuits/mod.rs:41``) and adaptive form (converge
to an L1 tolerance — the deliberate semantic extension BASELINE.json's north
star asks for).

All functions are jit-compiled with static shapes; iteration counts are
static (unrolled loop bounds) or carried as while_loop state.
"""

from __future__ import annotations

import time
from functools import partial
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..graph import EllOperator
from ..utils import trace


class Semiring(NamedTuple):
    """The pluggable (add, mul) algebra of one converge sweep.

    The power iteration's inner product generalizes: a sweep computes
    ``new_s[i] = add_j mul(w_ji, s[j])`` over the SAME compiled
    operator layouts — only the combine/reduce ops change. Members are
    module-level jnp callables, so the tuple is hashable and rides
    through ``jax.jit`` as a static argument (one compile per algebra,
    never per value).

    - ``add``: binary combiner (the scatter/tail form of the reduce);
    - ``mul``: edge-weight application to a source score;
    - ``reduce``: the axis form of ``add`` (``jnp.sum`` / ``jnp.max``);
    - ``zero``: identity of ``add`` — the value every pad lane must
      yield. Both shipped semirings use 0.0, which is only an identity
      for ``max`` over NONNEGATIVE scores: every non-(+,×) semiring
      here assumes the trust invariant ``s >= 0`` (normalized weights,
      nonnegative starts preserve it).

    ``plusmul`` is classic EigenTrust; the DEFAULT converge entry
    points never dispatch through this seam at all (the pre-existing
    kernels run verbatim, same jit signatures). ``maxplus`` is
    bottleneck trust (max-min / widest-path, the tropical variant of
    arXiv 1906.05793): a peer's score is the best bottleneck over all
    trust paths reaching it, ``s[i] = max_j min(w_ji, s[j])`` — no
    dangling redistribution or damping (path semantics, not mass
    conservation); invalid slots are masked to 0.
    """

    name: str
    add: Callable
    mul: Callable
    reduce: Callable
    zero: float


PLUSMUL = Semiring("plusmul", jnp.add, jnp.multiply, jnp.sum, 0.0)
MAXPLUS = Semiring("maxplus", jnp.maximum, jnp.minimum, jnp.max, 0.0)

SEMIRINGS = {"plusmul": PLUSMUL, "maxplus": MAXPLUS}


def resolve_semiring(semiring) -> Semiring:
    """``None`` / name / ``Semiring`` → ``Semiring`` (default (+,×))."""
    if semiring is None:
        return PLUSMUL
    if isinstance(semiring, Semiring):
        return semiring
    try:
        return SEMIRINGS[semiring]
    except KeyError:
        raise ValueError(
            f"unknown semiring {semiring!r} (have: "
            f"{sorted(SEMIRINGS)})") from None


def semiring_tail(sr: Semiring, arrs: dict, s, base):
    """Post-reduce tail of one sweep under ``sr``: the (+,×) algebra
    keeps the dangling-mass rank-1 correction + damping
    (:func:`dangling_and_damping` — mass conservation); path algebras
    have no mass to conserve, so the tail is just the valid mask.
    ``sr`` is static under jit — this branch never appears in the
    compiled graph."""
    if sr.name == "plusmul":
        return dangling_and_damping(arrs, s, base)
    return base * arrs["valid"]


def record_converge_stats(backend: str, iters: int, delta, seconds: float,
                          n: int | None = None,
                          semiring: str = "plusmul") -> None:
    """Shared converge observability: every backend (gather, routed,
    sharded) reports its exit through this one seam so the instruments
    cannot diverge. Emits

    - ``ptpu_converge_iterations{backend,semiring}`` — the iteration
      count the power method actually ran: the convergence signal the
      EigenTrust analyses (arXiv:1603.00589, 2606.11956) say governs
      score quality, previously observable nowhere;
    - ``ptpu_converge_residual{backend,semiring}`` — the final
      relative-L1 delta (adaptive runs only; fixed-iteration runs pass
      ``delta=None``);
    - ``ptpu_converge_sweep_seconds{backend,semiring}`` — mean per-sweep
      (operator-apply) wall time, total/iters. The sweeps run inside a
      jitted ``while_loop``, so per-sweep timing cannot be observed
      in-loop without breaking compilation — the mean is the honest
      host-side view.
    """
    iters = int(iters)
    trace.gauge("converge_iterations").set(iters, backend=backend,
                                           semiring=semiring)
    if delta is not None:
        trace.gauge("converge_residual").set(float(delta), backend=backend,
                                             semiring=semiring)
    if iters > 0:
        trace.histogram("converge_sweep_seconds").observe(
            seconds / iters, backend=backend, semiring=semiring)
    trace.event("converge.done", backend=backend, iterations=iters,
                semiring=semiring, seconds=round(seconds, 6),
                **({} if n is None else {"n": n}),
                **({} if delta is None else {"residual": float(delta)}))


def record_refresh_scope(mode: str) -> None:
    """The one seam that says HOW a refresh swept the graph — the
    four-mode ladder ``partial → sampled → full → rebuild``:
    ``mode="partial"`` — host numpy sweeps restricted to the dirty
    frontier plus its fan-in (O(dirty), tiny frontiers);
    ``mode="device_partial"`` — the same frontier-restricted sweeps run
    through the device segment-gather kernel
    (:func:`partial_sweep_device`; frontiers past
    ``device_partial_threshold``);
    ``mode="sampled"`` — partially-observed sweeps over a bounded
    sample set (frontier + importance-sampled closure under
    ``sample_budget``) with the neglected-propagation mass tracked
    against the L1 honesty budget;
    ``mode="full"`` — whole-operator device sweeps on the patched
    operator (the partial bounds or the budget were exhausted);
    ``mode="rebuild"`` — served by a fresh operator build (the initial
    anchor, or a re-anchor after a capacity wall / lost delta log).
    Emits ``ptpu_refresh_sweep_scope_total{mode}`` so an operator can
    see the ratio drift (a rising full share means churn windows
    outgrow the sublinear bounds; a rising rebuild share means the
    delta engine is thrashing on re-anchors)."""
    trace.counter("refresh_sweep_scope").inc(mode=mode)
    trace.event("refresh.sweep_scope", mode=mode)


def timed_converge(backend: str, n: int, edges: int, signature, call,
                   fixed_iterations: int | None = None,
                   semiring: str = "plusmul"):
    """The one instrumentation wrapper every ConvergeBackend runs its
    converge through (span + compile watch + stats — a single seam so
    the two backends cannot drift): executes ``call`` under the
    ``converge.edges`` span and a ``compile_watch`` keyed on
    ``signature`` (the jit-cache identity — a second compile for the
    same signature is a steady-state recompile), and BLOCKS on the
    result before closing the timer: the converge functions are jitted
    and return at dispatch, so an unblocked wall time would record
    dispatch cost, not compute. The caller blocks immediately
    afterwards anyway (``np.asarray``), so this costs nothing.

    ``call`` returns device ``scores`` in fixed-iteration mode (pass
    ``fixed_iterations``) or ``(scores, iters, delta)`` in adaptive
    mode; returns ``call``'s result unchanged."""
    t0 = time.perf_counter()
    c0 = trace.thread_compile_seconds()
    with trace.span("converge.edges", backend=backend, n=n, edges=edges):
        with trace.compile_watch("converge", signature=signature):
            out = call()
            jax.block_until_ready(out)
    # carve the XLA compile out of the window (the listener runs on
    # this thread): a cold shape would otherwise inflate the per-sweep
    # mean by the whole compile, which ptpu_xla_compile_seconds
    # already measures on its own
    compile_dt = trace.thread_compile_seconds() - c0
    dt = max(time.perf_counter() - t0 - compile_dt, 0.0)
    if fixed_iterations is not None:
        record_converge_stats(backend, fixed_iterations, None, dt, n=n,
                              semiring=semiring)
    else:
        _, iters, delta = out
        # topic-batched calls return per-topic vectors; the recorded
        # count/residual are the worst topic (the honest scalar view)
        iters = np.max(np.asarray(iters))
        delta = np.max(np.asarray(delta))
        record_converge_stats(backend, int(iters), float(delta), dt, n=n,
                              semiring=semiring)
    return out


def fetch_converged(out, fixed: bool, to_nodes=np.asarray):
    """The host end of a ``converge_edges`` call, under the
    ``converge.fetch`` span: the device-to-host copy of the scores,
    mapped to node order by ``to_nodes``, and in adaptive mode the
    iteration count and final delta. ``out`` is what
    :func:`timed_converge` returned; the span pairs with the callers'
    ``converge.stage`` span around the operator's staging."""
    with trace.span("converge.fetch"):
        if fixed:
            return to_nodes(np.asarray(out))
        scores, iters, delta = out
        return to_nodes(np.asarray(scores)), int(iters), float(delta)


def warm_start_scores(prev, n: int, valid, initial_score: float):
    """Project a previous score vector onto a (possibly grown) peer set —
    the warm-start seam of the incremental refresh loop
    (``protocol_tpu.service``), per "Analysis of Power Iteration with
    Partially Observed Matrix-vector Products" (PAPERS.md): when only a
    small slice of the opinion matrix changed, the previous fixed point
    is a far better starting vector than uniform, and the adaptive loop
    stops in a handful of iterations instead of O(log(1/tol)/gap).

    ``prev`` covers the FIRST ``len(prev)`` slots of the new id space
    (service ids are append-only); new and previously-unseen peers start
    at ``initial_score``; invalid slots are zeroed. The result is
    rescaled so total mass equals the cold-start invariant
    ``n_valid * initial_score`` — power iteration under the
    mass-conserving trust operator converges to the fixed point with the
    mass of its starting vector, so without the rescale a warm and a
    cold converge would disagree by a scale factor whenever the peer
    set changed. Returns a float64 numpy vector (callers cast at device
    transfer).
    """
    valid = np.asarray(valid, dtype=bool)
    if valid.shape != (n,):
        raise ValueError(f"valid mask must have shape ({n},)")
    s = np.full(n, float(initial_score), dtype=np.float64)
    m = min(len(prev), n)
    carried = np.asarray(prev[:m], dtype=np.float64)
    if not len(carried) or float((carried * valid[:m]).sum()) <= 0.0:
        # degenerate carry-over (nothing, or all-zero/invalid): a
        # rescale would dump the whole mass on the new peers — cold
        # uniform is the only sensible start
        return valid.astype(np.float64) * float(initial_score)
    s[:m] = carried
    s *= valid
    target = float(valid.sum()) * float(initial_score)
    return s * (target / float(s.sum()))


@jax.jit
def partial_sweep_device(s, f_idx, f_valid, f_dang, f_ext,
                         e_row, e_src, e_w, scal):
    """One frontier-restricted power-iteration sweep on device — the
    segment-gather kernel behind ``incremental.device``.

    The full sweep applies the whole operator; this evaluates the
    update ONLY for a frontier row set, from its gathered in-edge
    segments: ``e_src[k]``/``e_w[k]`` is the k-th in-edge (source node,
    true normalized weight) of frontier row ``e_row[k]``, built
    host-side from the delta engine's CSR slices plus the per-row COO
    tail indexes. One gather + two segment-sums + elementwise tail —
    O(frontier fan-in) device work instead of O(E).

    The dangling-mass rank-1 shift stays the lazily-materialized
    SCALAR the host partial refresher tracks (``partial.py`` — change
    the math there and mirror it here; the device-vs-host parity test
    catches drift): ``scal`` packs the per-sweep host scalars
    ``[uni, uni_next, d_now, denom, keep, alpha, n_valid, total]`` as
    one device array so value changes never retrace.

    Shapes are the jit-cache identity — callers pow2-pad ``f_*`` and
    ``e_*`` (the delta patch-batch discipline) so the cache stays
    O(log frontier · log fan-in). Pad rows point at a dummy slot of
    ``s`` with ``f_valid = f_dang = 0`` and pad edges carry weight 0,
    so every pad lane computes exactly 0 and the frontier scatter
    stays deterministic (duplicate dummy indices all write 0).

    XLA:CPU constraint note (this box compiles limb-engine graphs for
    many minutes): this kernel is a fixed, loop-free graph — gathers,
    two segment scatter-adds and elementwise math — so its compile is
    cheap at every bucket shape. Keep it that way: no Python-unrolled
    per-sweep loops in here (roll any future iteration into a
    ``lax.fori_loop`` body), and never let a host float leak in as a
    traced constant (everything value-like rides in ``scal``).

    Returns ``(s2, changed, l1, d_delta, vsum, negl)``:
    ``s2`` — s with the frontier rows updated (store representation:
    true = s + uni·valid); ``changed`` — per-frontier-row true-value
    delta (the host expands the frontier where |changed| > drop_eps);
    ``l1`` — Σ|changed|; ``d_delta`` — dangling-mass delta of the
    store update; ``vsum`` — Σ valid over the frontier; ``negl`` —
    Σ|changed|·f_ext, the neglected-propagation mass bound of the
    sampled mode (``f_ext`` = per-row external out-weight; zeros in
    the plain partial mode).
    """
    uni = scal[0]
    uni_next = scal[1]
    d_now = scal[2]
    denom = scal[3]
    keep = scal[4]
    alpha = scal[5]
    n_valid = scal[6]
    total = scal[7]
    base = jnp.zeros(f_idx.shape[0], s.dtype).at[e_row].add(e_w * s[e_src])
    in_wsum = jnp.zeros(f_idx.shape[0], s.dtype).at[e_row].add(e_w)
    s_f = s[f_idx]
    base_true = base + uni * in_wsum
    s_true = s_f + uni * f_valid
    corr = (d_now - f_dang * s_true) / denom
    new_true = base_true + corr * f_valid
    # alpha == 0 => keep == 1 and the pretrust term vanishes: computing
    # the damped form unconditionally is exactly the undamped update
    new_true = keep * new_true + alpha * (
        f_valid / jnp.maximum(n_valid, 1.0)) * total
    changed = new_true - s_true
    new_store = new_true - uni_next * f_valid
    s2 = s.at[f_idx].set(new_store)
    l1 = jnp.sum(jnp.abs(changed))
    d_delta = jnp.sum(f_dang * (new_store - s_f))
    vsum = jnp.sum(f_valid)
    negl = jnp.sum(jnp.abs(changed) * f_ext)
    return s2, changed, l1, d_delta, vsum, negl


def operator_arrays(
    op: EllOperator, dtype=jnp.float32, alpha: float = 0.0, pretrust=None
) -> dict:
    """Device-ready pytree of an EllOperator's array leaves.

    ``alpha``/``pretrust`` enable the damped iteration
    s ← (1-α)·(Cᵀs + dangling-correction) + α·p. α=0 (default) is the
    reference's undamped semantics (native.rs:319-329); α>0 is the standard
    EigenTrust pre-trust mixing (BASELINE.json north star) which guarantees
    geometric convergence at rate (1-α) regardless of graph spectrum.
    ``pretrust`` defaults to uniform over valid peers, scaled so total mass
    is conserved for any s with sum(s) = sum(pretrust).
    """
    if pretrust is None:
        pretrust = op.valid.astype('float64') / max(op.n_valid, 1)
    return {
        "bucket_idx": tuple(jnp.asarray(b) for b in op.bucket_idx),
        "bucket_val": tuple(jnp.asarray(b, dtype=dtype) for b in op.bucket_val),
        "row_pos": jnp.asarray(op.row_pos),
        "valid": jnp.asarray(op.valid, dtype=dtype),
        "dangling": jnp.asarray(op.dangling, dtype=dtype),
        "n_valid": jnp.asarray(float(op.n_valid), dtype=dtype),
        "alpha": jnp.asarray(float(alpha), dtype=dtype),
        "pretrust": jnp.asarray(pretrust, dtype=dtype),
    }


def dangling_and_damping(arrs: dict, s: jnp.ndarray, base: jnp.ndarray
                         ) -> jnp.ndarray:
    """Shared tail of every SpMV backend: the dangling-mass rank-1
    correction plus damped pre-trust mixing.

    Dangling peers redistribute uniformly to every *other* valid peer
    (reference native.rs:263-278, as an implicit rank-1 update). α=0 is
    the pure reference semantics; for α>0, pretrust is scaled by the
    current total mass so the conservation invariant holds for any α.
    Both the gather path here and ops.routed share this function so the
    semantics cannot desynchronize. One twin CANNOT share it: the
    host-side partial refresher (``protocol_tpu/incremental/partial.py``)
    applies this same correction frontier-restricted, with ``d_mass``
    tracked incrementally across sweeps — change the math here and
    mirror it there (the residual-parity test catches drift).
    """
    d_mass = jnp.sum(s * arrs["dangling"])
    denom = jnp.maximum(arrs["n_valid"] - 1.0, 1.0)
    corr = (d_mass - arrs["dangling"] * s) / denom
    propagated = base + corr * arrs["valid"]

    alpha = arrs["alpha"]
    total = jnp.sum(s * arrs["valid"])
    return (1.0 - alpha) * propagated + alpha * arrs["pretrust"] * total


def spmv(arrs: dict, s: jnp.ndarray) -> jnp.ndarray:
    """One application of the normalized trust operator: returns Cᵀs with
    the dangling-mass correction.

    Per bucket: gather source scores, weight, reduce along the padded
    width. Bucket outputs concatenate (plus a zero slot for in-degree-0
    rows) and a permutation gather restores row order.
    """
    parts = [
        (val * s[idx]).sum(axis=-1)
        for idx, val in zip(arrs["bucket_idx"], arrs["bucket_val"])
    ]
    parts.append(jnp.zeros((1,), dtype=s.dtype))
    flat = jnp.concatenate(parts)
    base = flat[arrs["row_pos"]]
    return dangling_and_damping(arrs, s, base)


def adaptive_loop(step, s0: jnp.ndarray, tol: float, max_iterations: int,
                  accel_every: int = 0):
    """Shared adaptive-convergence driver: iterate ``step`` until the
    relative L1 delta ≤ tol (or max_iterations). Every backend (dense,
    gather-sparse, routed) runs this exact loop so tolerance semantics
    and iteration counts cannot diverge between them.

    ``accel_every > 0`` applies a safeguarded rank-1 minimal-polynomial
    extrapolation every that many iterations: with consecutive
    differences Δ1, Δ2, estimate the dominant contraction ratio
    r = ⟨Δ2,Δ1⟩/⟨Δ1,Δ1⟩ and jump s ← s + (r/(1−r))·Δ2 — the geometric
    series the dominant error mode would still contribute. The jump is
    an affine combination of mass-conserving iterates, so conservation
    is exact; r is clamped to [0, 0.9] so a misestimate cannot blow up,
    and the stopping delta is always the *unextrapolated* step
    contraction, so the tolerance semantics are unchanged.

    Returns (scores, iterations_run, final_relative_delta).
    """
    if accel_every == 1:
        # d1 would span the previous jump, corrupting the ratio estimate;
        # every >= 2 keeps both differences as clean power-iteration steps
        raise ValueError("accel_every must be 0 (off) or >= 2")
    norm = jnp.maximum(jnp.sum(jnp.abs(s0)), 1.0)

    def cond(state):
        _, _, i, delta = state
        return (delta > tol) & (i < max_iterations)

    def body(state):
        s_prev, s, i, _ = state
        s_next = step(s)
        # the convergence check is the sweep's last phase: named with
        # the routed sweep's tail (ops.routed.spmv_routed)
        with jax.named_scope("tail"):
            delta = jnp.sum(jnp.abs(s_next - s)) / norm
        if accel_every:
            d1 = s - s_prev
            d2 = s_next - s
            den = jnp.sum(d1 * d1)
            r = jnp.sum(d2 * d1) / jnp.maximum(den, jnp.finfo(s.dtype).tiny)
            r = jnp.clip(r, 0.0, 0.9)
            # never jump on the stopping iteration — neither a tol stop
            # nor the max_iterations cap: the returned vector must be the
            # one the reported delta describes
            do_acc = (((i % accel_every) == accel_every - 1) & (i >= 1)
                      & (delta > tol) & (i + 1 < max_iterations))
            s_next = jnp.where(do_acc, s_next + (r / (1.0 - r)) * d2, s_next)
        return s, s_next, i + 1, delta

    _, s, iters, delta = lax.while_loop(
        cond, body,
        (s0, s0, jnp.int32(0), jnp.asarray(jnp.inf, s0.dtype)),
    )
    return s, iters, delta


@partial(jax.jit, static_argnames=("num_iterations",))
def converge_sparse_fixed(arrs: dict, s0: jnp.ndarray, num_iterations: int):
    """Reference-parity fixed-iteration power iteration on the sparse op."""
    return lax.fori_loop(0, num_iterations, lambda _, s: spmv(arrs, s), s0)


@partial(jax.jit, static_argnames=("max_iterations", "accel_every"))
def converge_sparse_adaptive(
    arrs: dict, s0: jnp.ndarray, tol: float = 1e-6, max_iterations: int = 100,
    accel_every: int = 0,
):
    """Iterate until the relative L1 delta ≤ tol (or max_iterations).

    Returns (scores, iterations_run, final_relative_delta).
    """
    return adaptive_loop(lambda s: spmv(arrs, s), s0, tol, max_iterations,
                         accel_every)


def spmv_semiring(arrs: dict, s: jnp.ndarray, sr: Semiring) -> jnp.ndarray:
    """One generalized sweep on the sparse (bucketed-ELL) operator:
    ``new_s[i] = add_j mul(w_ji, s[j])`` + the semiring tail. The SAME
    bucket layouts as :func:`spmv` — pad lanes carry ``idx=0, val=0``,
    so ``mul`` yields ``min(0, s[0]) = 0`` (nonnegative scores) or
    ``0·s[0] = 0``: exactly ``sr.zero``, and the reduce ignores them.
    The DEFAULT (+,×) entry points never route through here — this is
    the named-variant path only, so the existing jit signatures are
    untouched."""
    parts = [
        sr.reduce(sr.mul(val, s[idx]), axis=-1)
        for idx, val in zip(arrs["bucket_idx"], arrs["bucket_val"])
    ]
    parts.append(jnp.full((1,), sr.zero, dtype=s.dtype))
    flat = jnp.concatenate(parts)
    base = flat[arrs["row_pos"]]
    return semiring_tail(sr, arrs, s, base)


@partial(jax.jit, static_argnames=("sr", "num_iterations"))
def converge_sparse_fixed_semiring(arrs: dict, s0: jnp.ndarray,
                                   sr: Semiring, num_iterations: int):
    """Fixed-iteration twin of :func:`converge_sparse_fixed` under a
    pluggable semiring (static: one compile per algebra)."""
    return lax.fori_loop(0, num_iterations,
                         lambda _, s: spmv_semiring(arrs, s, sr), s0)


@partial(jax.jit, static_argnames=("sr", "max_iterations", "accel_every"))
def converge_sparse_adaptive_semiring(
    arrs: dict, s0: jnp.ndarray, sr: Semiring, tol: float = 1e-6,
    max_iterations: int = 100, accel_every: int = 0,
):
    """Adaptive twin of :func:`converge_sparse_adaptive` under a
    pluggable semiring — the same :func:`adaptive_loop` (max-min
    iteration is monotone per coordinate, so the L1 delta hits exactly
    0 at the fixed point and the tolerance stop is well-defined)."""
    return adaptive_loop(lambda s: spmv_semiring(arrs, s, sr), s0, tol,
                         max_iterations, accel_every)


@partial(jax.jit, static_argnames=("sr", "max_iterations"))
def converge_sparse_topics(arrs: dict, s0k: jnp.ndarray, sr: Semiring,
                           tol: float = 1e-6, max_iterations: int = 100):
    """Topic-batched adaptive converge: vmap K topic score-vectors
    ``s0k[K, n]`` through ONE sparse operator (TrustFlow-style
    topic-aware reputation, arXiv 2603.19452 — K contexts share the
    graph, differ in start/pre-trust vector). The while_loop batching
    rule select-masks per-topic updates, so each topic's trajectory is
    independent: a converged topic's vector stops changing while
    slower topics keep sweeping. Returns ``(scores[K, n], iters[K],
    delta[K])``; the operator (and its build cost) is paid once for
    all K."""
    return jax.vmap(
        lambda s0: adaptive_loop(lambda s: spmv_semiring(arrs, s, sr),
                                 s0, tol, max_iterations))(s0k)


@partial(jax.jit, static_argnames=("num_iterations",))
def converge_dense_fixed(c_norm: jnp.ndarray, s0: jnp.ndarray, num_iterations: int):
    """Dense fixed-iteration twin: s ← s @ C (row-stochastic C).

    ``s @ C`` computes new_s[i] = Σⱼ C[j,i]·s[j] — identical index
    convention to the reference loop (native.rs:322-326).
    """
    return lax.fori_loop(0, num_iterations, lambda _, s: s @ c_norm, s0)


@partial(jax.jit, static_argnames=("max_iterations",))
def converge_dense_adaptive(
    c_norm: jnp.ndarray, s0: jnp.ndarray, tol: float = 1e-6, max_iterations: int = 100
):
    return adaptive_loop(lambda s: s @ c_norm, s0, tol, max_iterations)
