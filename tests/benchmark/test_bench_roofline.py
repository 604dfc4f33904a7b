"""The roofline's work counts and peaks."""

import pytest

from benchmark import kronecker, roofline


def test_sweep_bytes_on_a_toy_graph():
    # 3 peers; edges 0->1 twice (merged), 1->1 (a loop, dropped), 2->0
    src, dst, val = [0, 0, 1, 2], [1, 1, 1, 0], [5, 7, 9, 3]
    edges, peers = kronecker.filtered_shape(3, src, dst, val)
    assert (edges, peers) == (2, 3)
    work = roofline.spmv_sweep_work(edges, peers)
    # a float32 weight and an int32 index per edge, the score vector
    # read once and written once
    assert work == {"flops": 4.0, "bytes": 2 * 8 + 3 * 8}


def test_least_time_is_bound_by_bandwidth():
    work = roofline.spmv_sweep_work(32_418_364, 2_097_152)
    seconds, bound = roofline.least_seconds(work, "TPU v5 lite")
    assert bound == "bytes"
    assert seconds == pytest.approx(work["bytes"] / 819e9)


def test_unknown_device_is_an_error():
    with pytest.raises(roofline.UnknownDevice):
        roofline.peaks("TPU v9 imaginary")
