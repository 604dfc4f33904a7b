"""Milliseconds per HTTP request inside the daemon: the program's
``service.http`` span (``service/http_api.py``) over the window, per
span. The window's only requests are the client's ``GET /score/<addr>``."""


def read(rec):
    span = rec.layer.get("spans", {}).get("service.http", {})
    if not span.get("count"):
        return None
    return 1e3 * span["total_s"] / span["count"]
