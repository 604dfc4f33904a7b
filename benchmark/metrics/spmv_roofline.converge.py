"""Share of the roofline of one sweep: the least time the chip needs
for the bytes of one sweep over the filtered graph (E edges, N peers;
``roofline.spmv_sweep_work``), over the device time of a sweep from the
trace. Bound by HBM bandwidth."""

from benchmark import roofline
from benchmark.metrics import load

sweep_ms = load("sweep_ms.converge")


def read(rec):
    ms = sweep_ms(rec)
    if not ms:
        return None
    work = roofline.spmv_sweep_work(rec.layer["edges"], rec.layer["peers"])
    least, _ = roofline.least_seconds(work, rec.device["kind"])
    return 100.0 * least / (ms * 1e-3)
