"""Executables the window needed and did not have: backend-compile
events (compiled, or loaded from the persistent cache) between the
window's start and its last completion."""
UNIT = "count"


def read(r):
    return r.compiles.programs
