"""Seeded Graph500 Kronecker edge generator.

Follows the Graph500 reference generator (``kronecker_generator.m`` of
the Graph500 specification): ``edge_factor * 2^scale`` edges, each
placed by ``scale`` independent quadrant choices with initiator
probabilities A, B, C (D = 1 - A - B - C), then the vertex labels are
permuted at random and the edge list shuffled. Edges stay directed
(src -> dst); self-loops and duplicate tuples are kept, as the
generator makes them (the consumer filters them). Values are integers
drawn uniformly from ``[value_min, value_max]``, one per edge.

Two seeds: ``structure_seed`` draws the quadrant choices and the values,
``label_seed`` the vertex permutation and the edge order. A benchmark
run fixes the structure in its configuration and takes the labels from
``--seed``: every run then gets the same graph up to relabeling (the
same degree sequence, so the same padded shapes and the same work), in
another order, and the program's routing plan is new each time.

Everything is drawn in fixed chunks from ``numpy`` generators, so a
pair of seeds gives the same graph on every machine.
"""

from __future__ import annotations

import numpy as np

CHUNK = 1 << 22  # edges per draw; fixed so the stream does not depend on memory


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per (seed, stream); any integer seed."""
    return np.random.default_rng(
        np.random.SeedSequence([seed & (2**64 - 1), stream]))


def kronecker_edges(scale: int, edge_factor: int, a: float, b: float,
                    c: float, structure_seed: int, label_seed: int,
                    value_min: int = 1, value_max: int = 255):
    """Returns ``(n, src, dst, val)``: int32 endpoints, uint8 values."""
    n = 1 << scale
    m = edge_factor * n
    rng = rng_for(structure_seed, 0)
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab
    src = np.zeros(m, dtype=np.int32)
    dst = np.zeros(m, dtype=np.int32)
    for lo in range(0, m, CHUNK):
        hi = min(m, lo + CHUNK)
        s = np.zeros(hi - lo, dtype=np.int32)
        d = np.zeros(hi - lo, dtype=np.int32)
        for level in range(scale):
            ii = rng.random(hi - lo, dtype=np.float32) > ab
            thresh = np.where(ii, np.float32(c_norm), np.float32(a_norm))
            jj = rng.random(hi - lo, dtype=np.float32) > thresh
            s |= ii.astype(np.int32) << level
            d |= jj.astype(np.int32) << level
        src[lo:hi] = s
        dst[lo:hi] = d
    val = rng.integers(value_min, value_max + 1, m, dtype=np.uint8)
    labels = rng_for(label_seed, 1)
    perm = labels.permutation(n).astype(np.int32)
    order = labels.permutation(m)
    return n, perm[src[order]], perm[dst[order]], val[order]


def graph_for(config: dict, seed: int):
    """The configuration's graph, labeled by ``seed``: ``(n, src, dst,
    val)``."""
    return kronecker_edges(config["scale"], config["edge_factor"],
                           config["initiator_a"], config["initiator_b"],
                           config["initiator_c"], config["structure_seed"],
                           seed, config["value_min"], config["value_max"])


def filtered_shape(n: int, src, dst, val) -> tuple[int, int]:
    """(E, N) of the filtered graph: self-loops and non-positive values
    dropped, duplicate (src, dst) tuples merged into one edge; N counts
    every peer. Independent of the program's own filter."""
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    keep = (src != dst) & (np.asarray(val) > 0)
    key = np.unique(src[keep] * n + dst[keep])
    return int(len(key)), int(n)
