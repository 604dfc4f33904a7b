"""Peaks of each device kind, and the work the algorithm needs.

A roofline share is the least time the chip could take for the work
(the larger of operations over peak operations per second and bytes
over peak bytes per second) over the measured time. The work comes
from the problem's own shape, never from the program's padded arrays,
so the yardstick reads the same whatever implements the sweep.
"""

from __future__ import annotations

# keyed by jax's device_kind. Source: Google Cloud documentation,
# "TPU v5e" (system architecture): 197 TFLOP/s bf16, 394 TOP/s int8,
# 16 GB HBM2 at 819 GB/s.
PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


class UnknownDevice(Exception):
    pass


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no peaks for device kind {device_kind!r}; add it to "
            f"benchmark/roofline.py with its source") from None


F32 = 4
I32 = 4


def spmv_sweep_work(edges: int, peers: int) -> dict:
    """One power-iteration sweep over a graph of ``edges`` filtered
    edges and ``peers`` peers, float32 scores and weights: per edge a
    weight and a source index are read and one multiply-add is done;
    the score vector is read once and written once."""
    return {"flops": 2.0 * edges,
            "bytes": float(edges * (F32 + I32) + peers * 2 * F32)}


def least_seconds(work: dict, device_kind: str) -> tuple[float, str]:
    """The least time the chip could take for ``work``, and its bound."""
    p = peaks(device_kind)
    t_flops = work["flops"] / p["flops_bf16"]
    t_bytes = work["bytes"] / p["hbm_bytes_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")
