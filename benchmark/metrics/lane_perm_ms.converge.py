"""Device milliseconds per sweep of the Clos lane gathers: the self time
of the operations of ``converge_routed_adaptive`` whose scope path holds
``clos.lane_perm`` (``ops/clos.py`` ``route_core``), over the sweeps the
window's converges ran."""

from benchmark import trace_layers

FUNCTION = "converge_routed_adaptive"
SCOPE = "clos.lane_perm"


def read(rec):
    layers = trace_layers.of(rec)
    sweeps = sum(rec.layer.get("iterations") or [])
    if layers is None or not sweeps:
        return None
    seconds = layers.scope_seconds(FUNCTION, SCOPE)
    return None if seconds is None else 1e3 * seconds / sweeps
