"""Milliseconds per converge of the host fetch of the scores: the
program's ``converge.fetch`` span (the device-to-host copy, the
iteration count and delta, ``scores_for_nodes``), read as a host event
of the profiler's trace inside the window, per event."""

from benchmark import trace_layers

SPAN = "converge.fetch"


def read(rec):
    layers = trace_layers.of(rec)
    return None if layers is None else layers.span_ms(SPAN)
