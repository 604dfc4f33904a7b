"""The benchmark's Graph500 Kronecker generator."""

import numpy as np
import pytest

from benchmark import kronecker


def test_same_seeds_same_graph():
    a = kronecker.kronecker_edges(10, 16, 0.57, 0.19, 0.19, 22, 2**31 + 5)
    b = kronecker.kronecker_edges(10, 16, 0.57, 0.19, 0.19, 22, 2**31 + 5)
    for x, y in zip(a[1:], b[1:]):
        np.testing.assert_array_equal(x, y)
    c = kronecker.kronecker_edges(10, 16, 0.57, 0.19, 0.19, 22, 2**31 + 6)
    assert not np.array_equal(a[1], c[1])


def test_label_seed_relabels_one_structure():
    """Another label seed gives the same graph up to relabeling: the
    same degree sequences and the same (src-degree, dst-degree, value)
    multiset."""
    def signature(graph):
        n, src, dst, val = graph
        out_deg = np.bincount(src, minlength=n)
        in_deg = np.bincount(dst, minlength=n)
        rows = np.stack([out_deg[src], in_deg[dst], val.astype(np.int64)])
        return (np.sort(out_deg), np.sort(in_deg),
                rows[:, np.lexsort(rows)])

    a = kronecker.kronecker_edges(10, 16, 0.57, 0.19, 0.19, 22, 1)
    b = kronecker.kronecker_edges(10, 16, 0.57, 0.19, 0.19, 22, 2)
    for x, y in zip(signature(a), signature(b)):
        np.testing.assert_array_equal(x, y)
    c = kronecker.kronecker_edges(10, 16, 0.57, 0.19, 0.19, 23, 1)
    assert not np.array_equal(signature(a)[0], signature(c)[0])


def test_sizes_and_value_range():
    n, src, dst, val = kronecker.kronecker_edges(11, 16, 0.57, 0.19, 0.19, 3,
                                                 4)
    assert n == 2048 and len(src) == len(dst) == len(val) == 16 * 2048
    assert src.min() >= 0 and src.max() < n and dst.max() < n
    assert val.min() >= 1 and val.max() <= 255


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_degree_skew(seed):
    """At scale 14 the initiator (0.57, 0.19, 0.19) gives a heavy-tailed
    out-degree: the largest is hundreds of times the mean, and about
    a third of the peers have no out-edge (half at scale 21)."""
    n, src, dst, val = kronecker.kronecker_edges(14, 16, 0.57, 0.19, 0.19,
                                                 seed, 7)
    deg = np.bincount(src, minlength=n)
    assert 100 < deg.max() / deg.mean() < 1000
    assert 0.25 < np.mean(deg == 0) < 0.45


def test_filtered_shape_merges_duplicates_and_loops():
    src = np.array([0, 0, 1, 2, 2])
    dst = np.array([1, 1, 1, 0, 1])
    val = np.array([3, 4, 5, 1, 2])
    assert kronecker.filtered_shape(4, src, dst, val) == (3, 4)
