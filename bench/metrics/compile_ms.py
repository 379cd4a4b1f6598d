"""Compile time per query: the seconds JAX spent tracing, lowering and
compiling inside the window (the harness's compile clock; the program's
``launch`` spans carry the same seconds per program), over the queries
completed, in ms."""
UNIT = "ms"


def read(r):
    if not r.completed:
        return None
    return 1e3 * r.compiles.compile_s / r.completed
