"""Milliseconds per converge of the host staging of the operator: the
program's ``converge.stage`` span (``routed_arrays`` and the upload of
the start vector in ``JaxRoutedBackend.converge_edges``), read as a host
event of the profiler's trace inside the window, per event."""

from benchmark import trace_layers

SPAN = "converge.stage"


def read(rec):
    layers = trace_layers.of(rec)
    return None if layers is None else layers.span_ms(SPAN)
