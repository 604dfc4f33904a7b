"""Device milliseconds per sweep: the device time of the jitted
converge program (``converge_routed_adaptive``) in the trace, over the
sweeps the window's converges ran."""

PROGRAM = "converge_routed_adaptive"


def read(rec):
    if rec.trace is None:
        return None
    seconds, runs = rec.trace.program_seconds(PROGRAM)
    sweeps = sum(rec.layer.get("iterations") or [])
    if not runs or not sweeps:
        return None
    return 1e3 * seconds / sweeps
