"""The ConvergeBackend seam.

SURVEY.md §7 / BASELINE.json north star: cut a backend boundary at the
reference's ``EigenTrustSet::converge`` so the exact small-set semantics
(``backend=native``) and the TPU path (``backend=jax``) are interchangeable
consumers of the same filtered opinion data.

All backends consume the *filtered* opinion matrix (redistribution rows
already materialized by ``EigenTrustSet.filter_peers_ops`` — or, at scale,
the raw edge list which ``graph.filter_edges`` filters with identical
semantics) and return real-valued scores. The field-exact path stays on
``EigenTrustSet.converge`` itself — field scores are not float-approximable
(SURVEY.md §7.3) and are computed host-side or via ``ops.fieldops`` batched
field kernels for witnesses.
"""

from __future__ import annotations

from abc import ABC, abstractmethod
from fractions import Fraction
from typing import Sequence

import numpy as np


class ConvergeBackend(ABC):
    """Strategy interface for the real-valued convergence computation."""

    @abstractmethod
    def converge(
        self,
        matrix: Sequence[Sequence[float]],
        initial_score: float,
        num_iterations: int,
    ) -> np.ndarray:
        """Run the power iteration on a filtered opinion matrix."""


class NativeRationalBackend(ConvergeBackend):
    """Exact rational arithmetic — the correctness oracle
    (converge_rational, dynamic_sets/native.rs:340-392)."""

    def converge(self, matrix, initial_score, num_iterations):
        exact = self.converge_exact(matrix, initial_score, num_iterations)
        return np.array([float(x) for x in exact])

    def converge_exact(self, matrix, initial_score, num_iterations):
        """Same, returning the Fractions (for threshold decomposition).

        Float entries are lifted exactly via ``Fraction(v)`` (binary
        expansion). Like all backends, expects a *filtered* opinion matrix
        (zero row ⇔ empty slot that receives no trust).
        """
        n = len(matrix)
        norm = []
        for row in matrix:
            row_sum = sum(Fraction(v) for v in row) or Fraction(1)
            norm.append([Fraction(v) / row_sum for v in row])
        s = [Fraction(initial_score)] * n
        for _ in range(num_iterations):
            s = [sum(norm[j][i] * s[j] for j in range(n)) for i in range(n)]
        return s


class JaxDenseBackend(ConvergeBackend):
    """Dense device power iteration — MXU matvec per step. Right for
    fully-connected sets up to a few thousand peers."""

    def __init__(self, dtype=None):
        import jax.numpy as jnp

        self.dtype = dtype or jnp.float32

    def converge(self, matrix, initial_score, num_iterations):
        import jax.numpy as jnp

        from .graph import dense_normalized
        from .ops.converge import converge_dense_fixed

        m = np.asarray(matrix, dtype=np.float64)
        c = jnp.asarray(dense_normalized(m), dtype=self.dtype)
        has_row = m.sum(axis=1) > 0
        s0 = jnp.asarray(has_row, dtype=self.dtype) * float(initial_score)
        return np.asarray(converge_dense_fixed(c, s0, num_iterations))


class JaxSparseBackend(ConvergeBackend):
    """Bucketed-ELL gather-SpMV power iteration — the scale path.

    Accepts a dense filtered matrix (converted to edges) through the
    common interface; large graphs should use :meth:`converge_edges`
    directly with raw edge arrays.
    """

    def __init__(self, dtype=None):
        import jax.numpy as jnp

        self.dtype = dtype or jnp.float32

    def converge(self, matrix, initial_score, num_iterations):
        m = np.asarray(matrix, dtype=np.float64)
        src, dst = np.nonzero(m)
        # Contract: `matrix` is a *filtered* opinion matrix (zero row ⇔
        # empty slot). A zero-row peer that still receives trust would be
        # interpreted differently by the edge path (its in-edges dropped,
        # trusters renormalized) than by the dense/rational twins (mass
        # received then vanishing) — reject rather than silently diverge.
        valid = m.sum(axis=1) > 0
        receives = m.sum(axis=0) > 0
        bad = np.nonzero(~valid & receives)[0]
        if len(bad):
            raise ValueError(
                f"matrix is not filtered: zero-row peers {bad.tolist()} still "
                "receive trust; run it through EigenTrustSet.filter_peers_ops "
                "or use converge_edges with an explicit valid mask"
            )
        return self.converge_edges(
            m.shape[0], src, dst, m[src, dst], valid, initial_score, num_iterations
        )

    def converge_edges(
        self, n, src, dst, val, valid, initial_score, num_iterations, tol=None,
        alpha: float = 0.0, s0=None, semiring=None,
    ):
        """``s0`` (node-order, length n) warm-starts the power iteration —
        pair with :func:`ops.converge.warm_start_scores` to project a
        previous score vector onto the current peer set. Omitted, the
        cold uniform start (valid·initial_score) is used.

        ``semiring`` selects the sweep algebra (``ops.converge.SEMIRINGS``
        name or a ``Semiring``). The default — ``None`` / ``"plusmul"``
        — runs the PRE-EXISTING (+,×) kernels verbatim: same functions,
        same jit signatures, byte-identical iterate trajectory. Named
        variants (``"maxplus"`` bottleneck trust) run through the
        semiring twins over the same operator layouts."""
        import jax.numpy as jnp

        from .graph import build_operator
        from .ops.converge import (
            converge_sparse_adaptive,
            converge_sparse_adaptive_semiring,
            converge_sparse_fixed,
            converge_sparse_fixed_semiring,
            fetch_converged,
            operator_arrays,
            resolve_semiring,
            timed_converge,
        )
        from .utils import trace

        sr = resolve_semiring(semiring)
        op = build_operator(n, src, dst, val, valid)
        with trace.span("converge.stage", backend="jax-sparse"):
            arrs = operator_arrays(op, dtype=self.dtype, alpha=alpha)
            if s0 is None:
                s0 = jnp.asarray(op.valid, dtype=self.dtype) * float(
                    initial_score)
            else:
                s0 = jnp.asarray(np.asarray(s0), dtype=self.dtype)
        # the jit-cache identity of the converge call: bucket geometry +
        # dtype + static loop bound. A compile for a signature already
        # compiled once is a shape leak (steady-state recompile).
        sig = ("sparse", n, tuple(b.shape for b in op.bucket_idx),
               str(s0.dtype), "fixed" if tol is None else "adaptive",
               int(num_iterations))
        if sr.name != "plusmul":
            sig = sig + (sr.name,)
            if tol is None:
                def call():
                    return converge_sparse_fixed_semiring(
                        arrs, s0, sr, num_iterations)
            else:
                def call():
                    return converge_sparse_adaptive_semiring(
                        arrs, s0, sr, tol=tol, max_iterations=num_iterations)
        elif tol is None:
            def call():
                return converge_sparse_fixed(arrs, s0, num_iterations)
        else:
            def call():
                return converge_sparse_adaptive(
                    arrs, s0, tol=tol, max_iterations=num_iterations)
        out = timed_converge(
            "jax-sparse", n, len(src), sig, call,
            fixed_iterations=num_iterations if tol is None else None,
            semiring=sr.name)
        return fetch_converged(out, fixed=tol is None)

    def converge_topics(
        self, n, src, dst, val, valid, s0_topics, max_iterations,
        tol=1e-6, alpha: float = 0.0, semiring=None,
    ):
        """Topic-batched adaptive converge: vmap the K node-order topic
        vectors ``s0_topics[K, n]`` through ONE operator (one build,
        one compiled sweep — the TrustFlow-style amortization). Each
        topic's trajectory is independent (while_loop batching
        select-masks converged topics). Returns
        ``(scores[K, n], iters[K], delta[K])`` as numpy."""
        import jax.numpy as jnp

        from .graph import build_operator
        from .ops.converge import (
            converge_sparse_topics,
            operator_arrays,
            resolve_semiring,
            timed_converge,
        )
        from .utils import trace

        sr = resolve_semiring(semiring)
        s0k = np.asarray(s0_topics, dtype=np.float64)
        if s0k.ndim != 2 or s0k.shape[1] != n:
            raise ValueError(
                f"s0_topics must be [K, {n}] (got {s0k.shape})")
        op = build_operator(n, src, dst, val, valid)
        with trace.span("converge.stage", backend="jax-sparse"):
            arrs = operator_arrays(op, dtype=self.dtype, alpha=alpha)
            s0k = jnp.asarray(s0k, dtype=self.dtype)
        sig = ("sparse-topics", n, int(s0k.shape[0]),
               tuple(b.shape for b in op.bucket_idx), str(s0k.dtype),
               int(max_iterations), sr.name)
        scores, iters, delta = timed_converge(
            "jax-sparse", n, len(src), sig,
            lambda: converge_sparse_topics(
                arrs, s0k, sr, tol=tol, max_iterations=max_iterations),
            semiring=sr.name)
        with trace.span("converge.fetch"):
            return (np.asarray(scores), np.asarray(iters),
                    np.asarray(delta))


class JaxRoutedBackend(JaxSparseBackend):
    """Clos-routed SpMV power iteration (ops/routed.py) — the large-graph
    path: no general gathers; the sparse transpose runs as a permutation
    network of lane shuffles at streaming bandwidth. Same converge
    semantics as :class:`JaxSparseBackend`; pays a one-time host routing
    compilation per graph (reusable via ``RoutedOperator.save``)."""

    def converge_edges(
        self, n, src, dst, val, valid, initial_score, num_iterations, tol=None,
        alpha: float = 0.0, operator=None, s0=None, semiring=None,
    ):
        import jax.numpy as jnp

        from .ops.converge import (
            fetch_converged,
            resolve_semiring,
            timed_converge,
        )
        from .ops.routed import (
            build_routed_operator,
            converge_routed_adaptive,
            converge_routed_adaptive_semiring,
            converge_routed_fixed,
            converge_routed_fixed_semiring,
            routed_arrays,
        )
        from .utils import trace

        sr = resolve_semiring(semiring)
        op = operator
        if op is None:
            op = build_routed_operator(n, src, dst, val, valid)
        with trace.span("converge.stage", backend="jax-routed"):
            arrs, static = routed_arrays(op, dtype=self.dtype, alpha=alpha)
            if s0 is None:
                # the cold start from the resident 0/1 mask: exactly
                # op.initial_scores, with nothing uploaded
                s0 = arrs["valid"] * float(initial_score)
            else:
                # node-order warm start → state-slot order
                s0 = jnp.asarray(op.scores_from_nodes(np.asarray(s0),
                                                      dtype=self.dtype))
        # the static tuple IS the routed jit cache key (hashable by
        # construction) — plus dtype and the static loop bound
        sig = ("routed", static, str(s0.dtype),
               "fixed" if tol is None else "adaptive", int(num_iterations))
        if sr.name != "plusmul":
            # the named-variant path: the SAME compiled route plans,
            # semiring twins for broadcast/reduce only
            sig = sig + (sr.name,)
            if tol is None:
                def call():
                    return converge_routed_fixed_semiring(
                        arrs, static, s0, sr, num_iterations)
            else:
                def call():
                    return converge_routed_adaptive_semiring(
                        arrs, static, s0, sr, tol=tol,
                        max_iterations=num_iterations)
        elif tol is None:
            def call():
                return converge_routed_fixed(arrs, static, s0,
                                             num_iterations)
        else:
            def call():
                return converge_routed_adaptive(
                    arrs, static, s0, tol=tol, max_iterations=num_iterations)
        out = timed_converge(
            "jax-routed", n, int(op.nnz), sig, call,
            fixed_iterations=num_iterations if tol is None else None,
            semiring=sr.name)
        return fetch_converged(out, fixed=tol is None,
                               to_nodes=op.scores_for_nodes)

    def converge_topics(
        self, n, src, dst, val, valid, s0_topics, max_iterations,
        tol=1e-6, alpha: float = 0.0, operator=None, semiring=None,
    ):
        """Routed topic batch: K node-order topic vectors vmapped
        through ONE routed operator — exactly one routing-plan build
        (one ``ptpu_routed_plan_build_seconds`` sample) serves all K
        topics. Returns ``(scores[K, n], iters[K], delta[K])``."""
        import jax.numpy as jnp

        from .ops.converge import resolve_semiring, timed_converge
        from .ops.routed import (
            build_routed_operator,
            converge_routed_topics,
            routed_arrays,
        )
        from .utils import trace

        sr = resolve_semiring(semiring)
        s0k = np.asarray(s0_topics, dtype=np.float64)
        if s0k.ndim != 2 or s0k.shape[1] != n:
            raise ValueError(
                f"s0_topics must be [K, {n}] (got {s0k.shape})")
        op = operator
        if op is None:
            op = build_routed_operator(n, src, dst, val, valid)
        with trace.span("converge.stage", backend="jax-routed"):
            arrs, static = routed_arrays(op, dtype=self.dtype, alpha=alpha)
            s0k = jnp.asarray(
                np.stack([op.scores_from_nodes(row, dtype=self.dtype)
                          for row in s0k]))
        sig = ("routed-topics", static, int(s0k.shape[0]),
               str(s0k.dtype), int(max_iterations), sr.name)
        scores, iters, delta = timed_converge(
            "jax-routed", n, int(op.nnz), sig,
            lambda: converge_routed_topics(
                arrs, static, s0k, sr, tol=tol,
                max_iterations=max_iterations),
            semiring=sr.name)
        with trace.span("converge.fetch"):
            return (np.stack([op.scores_for_nodes(row)
                              for row in np.asarray(scores)]),
                    np.asarray(iters), np.asarray(delta))
