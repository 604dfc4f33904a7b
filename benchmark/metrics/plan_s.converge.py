"""Seconds of the fresh routing-plan build in set-up: the program's
``routed.plan_build`` span (``ops/routed.py`` build, ``ops/clos.py``
and the native planner)."""


def read(rec):
    return rec.layer.get("plan_build_s")
