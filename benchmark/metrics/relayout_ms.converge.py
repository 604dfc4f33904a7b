"""Device milliseconds per sweep of the relayouts between Clos stages:
the self time of the operations of ``converge_routed_adaptive`` whose
scope path holds ``clos.relayout`` (the reshape / transpose / reshape of
``ops/clos.py`` ``route_core``), over the sweeps the window's converges
ran."""

from benchmark import trace_layers

FUNCTION = "converge_routed_adaptive"
SCOPE = "clos.relayout"


def read(rec):
    layers = trace_layers.of(rec)
    sweeps = sum(rec.layer.get("iterations") or [])
    if layers is None or not sweeps:
        return None
    seconds = layers.scope_seconds(FUNCTION, SCOPE)
    return None if seconds is None else 1e3 * seconds / sweeps
