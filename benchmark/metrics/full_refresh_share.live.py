"""Share of the window's refreshes swept over the whole graph: the
program's counter ``refresh_sweep_scope{mode}`` (``ops/converge.py``
``record_refresh_scope``), ``full`` over all modes (``partial``,
``device_partial``, ``sampled``, ``full``, ``rebuild``), counted over
the window."""


def read(rec):
    scopes = rec.layer.get("scopes") or {}
    total = sum(scopes.values())
    if not total:
        return None
    return 100.0 * scopes.get("full", 0.0) / total
