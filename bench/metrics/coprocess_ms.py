"""Co-processing time per query: the sum of ``Timing.phase_s`` over the
query's outcomes (partition, build, probe, join, group-by; every stage of
a pipeline), averaged over the queries completed."""
UNIT = "ms"


def read(r):
    if not r.layers:
        return None
    return 1e3 * sum(x["coprocess_s"] for x in r.layers) / len(r.layers)
