"""Milliseconds per refresh: the program's ``service.refresh`` span
(``service/refresh.py``: the delta engine's ladder, the full sweep on
the patched operator through the shared routed converge) over the
window, per span."""


def read(rec):
    span = rec.layer.get("spans", {}).get("service.refresh", {})
    if not span.get("count"):
        return None
    return 1e3 * span["total_s"] / span["count"]
