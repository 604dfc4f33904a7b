"""The benchmark's float64 reference and its bfloat16 control."""

import numpy as np

from benchmark import kronecker, reference


def dense_eigentrust(n, src, dst, val, alpha, initial, iterations=2000):
    """EigenTrust written out densely: row-normalized opinion matrix,
    dangling rows spread over every other peer, damping toward uniform
    pre-trust times the total mass."""
    c = np.zeros((n, n))
    for s, d, v in zip(src, dst, val):
        if s != d and v > 0:
            c[s, d] += v
    for i in range(n):
        row = c[i].sum()
        if row > 0:
            c[i] /= row
        else:
            c[i] = 1.0 / (n - 1)
            c[i, i] = 0.0
    t = np.full(n, float(initial))
    for _ in range(iterations):
        t = (1 - alpha) * (c.T @ t) + alpha * t.sum() / n
    return t


def small_graph(seed=4):
    n, src, dst, val = kronecker.kronecker_edges(7, 8, 0.57, 0.19, 0.19, seed,
                                                 seed)
    return n, src, dst, val


def test_reference_matches_dense_numpy():
    n, src, dst, val = small_graph()
    ref, iterations = reference.eigentrust_f64(n, src, dst, val, 0.15,
                                               1000.0, tol=1e-13)
    dense = dense_eigentrust(n, src, dst, val, 0.15, 1000.0)
    assert iterations > 5
    assert reference.rel_l1(ref, dense) < 1e-11
    np.testing.assert_allclose(ref.sum(), n * 1000.0, rtol=1e-12)


def test_reference_without_damping_on_a_ring():
    """Three peers in a ring trust each other equally: uniform scores."""
    ref, _ = reference.eigentrust_f64(3, [0, 1, 2], [1, 2, 0], [5, 5, 5],
                                      0.0, 1000.0)
    np.testing.assert_allclose(ref, [1000.0] * 3)


def test_bf16_control_departs_from_the_reference():
    n, src, dst, val = kronecker.kronecker_edges(12, 16, 0.57, 0.19, 0.19, 9,
                                                 9)
    ref, _ = reference.eigentrust_f64(n, src, dst, val, 0.15, 1000.0)
    low, _ = reference.eigentrust_bf16(n, src, dst, val, 0.15, 1000.0,
                                       1e-6, 500)
    assert reference.rel_l1(low, ref) > 5e-4
