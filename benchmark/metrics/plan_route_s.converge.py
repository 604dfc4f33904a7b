"""Seconds of the native Clos planner in the fresh plan of set-up: the
program's ``routed.plan.route`` span (``plan_routes`` in
``ops/routed.py``), a child of the ``routed.plan_build`` span that
``plan_s.converge`` reads. Read from the program's tracer in the
process the runner set up."""

SPAN = "routed.plan.route"


def read(rec):
    from protocol_tpu.utils import trace

    return trace.summary().get(SPAN, {}).get("total_s")
