"""Planner time per query: the service tracer's ``plan`` spans that
started in the window, summed, over the queries completed."""
UNIT = "ms"


def read(r):
    if not r.completed:
        return None
    return 1e3 * r.plan_s / r.completed
