"""Milliseconds per ingest batch of folding it into the opinion graph:
the program's ``service.graph_apply`` span (``OpinionGraph.apply``,
``service/state.py``) over the window, per span."""


def read(rec):
    span = rec.layer.get("spans", {}).get("service.graph_apply", {})
    if not span.get("count"):
        return None
    return 1e3 * span["total_s"] / span["count"]
