"""Serve a stream of heterogeneous join queries through the engine.

Demonstrates the full loop: workload generation (uniform / zipf /
selectivity / hot-table mix), admission into ``JoinQueryService``,
cost-model planning per query (scheme + SHJ-vs-PHJ), build-table cache
reuse, and the online calibration feedback — with every result verified
against the oracle.

    PYTHONPATH=src python examples/engine_serve.py [--queries 24]
"""
import argparse
import time

from repro.compile_cache import enable_compile_cache
from repro.core import CoProcessor, join_oracle
from repro.engine import JoinQueryService, QueryPlanner, make_workload


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--queries", type=int, default=24)
    ap.add_argument("--base-tuples", type=int, default=16384)
    ap.add_argument("--workers", type=int, default=2)
    args = ap.parse_args()

    cp = CoProcessor()
    print("calibrating unit costs on this host (paper §4.2)...")
    planner = QueryPlanner.calibrated(cp, n=16384, reps=2, delta=0.1)
    workload = make_workload("mixed", num_queries=args.queries,
                             base_tuples=args.base_tuples, seed=42)
    print(f"serving {len(workload)} queries "
          f"(C={cp.c.size} dev, G={cp.g.size} dev, "
          f"workers={args.workers})\n")
    t0 = time.perf_counter()
    with JoinQueryService(cp=cp, planner=planner,
                          num_workers=args.workers) as svc:
        outcomes = svc.run(workload)
        elapsed = time.perf_counter() - t0
        hdr = (f"{'id':>3} {'tag':<10} {'|R|':>7} {'|S|':>7} "
               f"{'plan':<10} {'cache':<5} {'ms':>8} {'matches':>8}")
        print(hdr + "\n" + "-" * len(hdr))
        for q, o in zip(workload, outcomes):
            exp = join_oracle(q.build, q.probe)
            assert (o.result.valid_pairs() == exp).all(), q.query_id
            plan = f"{o.plan.algorithm}/{o.plan.scheme}"
            print(f"{q.query_id:>3} {q.tag:<10} {q.build.size:>7} "
                  f"{q.probe.size:>7} {plan:<10} "
                  f"{'HIT' if o.cache_hit else '':<5} "
                  f"{o.wall_s * 1e3:>8.1f} {int(o.result.count):>8}")
        st = svc.stats()
    print(f"\nall {len(outcomes)} results verified against the oracle")
    print(f"throughput: {len(outcomes) / elapsed:.2f} queries/s")
    c = st["cache"]
    print(f"cache: {c['hits']} hits / {c['hits'] + c['misses']} lookups "
          f"(rate {c['hit_rate']:.0%}), {c['bytes'] / 2**20:.1f} MiB "
          f"resident, {c['evictions']} evictions")
    print(f"plans: {st['planner']['plan_counts']}")
    print("online unit-cost scales:",
          {k: round(v["scale"], 2)
           for k, v in st["planner"]["online"].items()})


if __name__ == "__main__":
    main()
