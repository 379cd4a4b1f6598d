"""Replay a join workload under seeded fault injection.

Installs a :class:`FaultInjector` whose per-site schedule is derived
from one seed — kernel launches raise, H2D transfers stall, one worker
thread dies — and drives a stream of joins through ``JoinQueryService``
while the faults fire.  The service's recovery ladder (bounded retries
-> degraded plan -> circuit breaker -> NumPy reference path) absorbs
every transient fault: the demo asserts that each query either succeeds
row-exactly against the NumPy oracle or fails with a *structured*
``Backpressure`` error, then prints the resilience counters and breaker
states the chaos left behind.

Because the injector is seed-deterministic, re-running with the same
``--seed`` replays the identical fault schedule — which is how the
chaos section of ``benchmarks/slo_bench.py`` stays debuggable.

    PYTHONPATH=src python examples/chaos_replay.py [--seed 7] [--queries 12]
"""
import argparse

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import CoProcessor, uniform_relation, unique_relation
from repro.engine import (FaultInjector, FaultSpec, JoinQuery,
                         JoinQueryService, QueryPlanner, QueueFull,
                         injected)
from repro.ops.join_variants import join_variant_oracle


def result_rows(result):
    """(probe_rid, build_rid) pairs, sorted — the oracle's shape."""
    n = int(result.count)
    rows = np.stack([np.asarray(result.probe_rid[:n]),
                     np.asarray(result.build_rid[:n])], axis=1)
    return rows[np.lexsort((rows[:, 1], rows[:, 0]))]


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--queries", type=int, default=12)
    ap.add_argument("--rows", type=int, default=4096)
    args = ap.parse_args()

    cp = CoProcessor()
    planner = QueryPlanner(delta=0.25)

    # The fault schedule: every 3rd kernel launch raises (transient, so
    # the ladder engages), 20% of H2D transfers stall 2ms, and the 2nd
    # worker-loop iteration dies (the supervisor restarts it).
    inj = FaultInjector(seed=args.seed, sites={
        "kernel": FaultSpec(mode="raise", every=3, max_faults=6),
        "h2d": FaultSpec(mode="delay", p=0.2, delay_s=0.002),
        "worker": FaultSpec(mode="raise", at=(2,)),
    })

    svc = JoinQueryService(cp=cp, planner=planner, num_workers=2,
                           preempt=True)
    queries, waits = [], []
    for i in range(args.queries):
        b = unique_relation(args.rows, seed=i)
        s = uniform_relation(args.rows, key_range=args.rows,
                             seed=100 + i)
        queries.append(JoinQuery(build=b, probe=s, query_id=i,
                                 max_out=8 * args.rows))

    structured = unstructured = exact = 0
    with injected(inj):                       # uninstalls on exit
        for q in queries:
            waits.append(svc.submit(q))
        for q, w in zip(queries, waits):
            try:
                out = w()
            except QueueFull:                 # Backpressure family
                structured += 1
                continue
            except Exception as e:            # would be a ladder bug
                unstructured += 1
                print(f"  q{q.query_id} UNSTRUCTURED: {e!r}")
                continue
            want = join_variant_oracle(q.build, q.probe, "inner")
            ok = np.array_equal(result_rows(out.result), want)
            exact += ok
            note = (" [reference path]" if out.timing is not None and
                    out.timing.notes.get("reference_path") else "")
            print(f"  q{q.query_id} {out.plan.algorithm}/{out.plan.scheme}"
                  f" rows={int(out.result.count)}"
                  f" exact={bool(ok)}{note}")
    svc.close(drain=True)

    res = svc.stats()["resilience"]
    print(f"\nfaults fired: {inj.stats()['fired']}")
    print(f"retries={res['retries']} worker_restarts="
          f"{res['worker_restarts']} preemptions={res['preemptions']}")
    print(f"breakers: { {k: v['state'] for k, v in res['breakers'].items()} }")
    print(f"{exact}/{args.queries - structured} row-exact, "
          f"{structured} structured failures, {unstructured} unstructured")
    assert unstructured == 0, "every failure must be structured"
    assert exact == args.queries - structured, "survivors must be exact"
    print("chaos replay clean: structured failures only, row-exact output")


if __name__ == "__main__":
    main()
