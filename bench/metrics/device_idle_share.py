"""Share of the measured window in which the chip ran no operation:
1 - (union of device-op intervals) / window, from the profiler trace."""
UNIT = "%"


def read(r):
    if r.trace is None:
        return None
    return 100.0 * r.trace["idle_share"]
