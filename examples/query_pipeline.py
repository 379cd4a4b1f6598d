"""Run a multi-join star query through the query pipeline.

Demonstrates the full stack: a declarative ``Query`` (fact table, filtered
dimensions, count sink), cost-model join ordering (chosen vs textual vs
worst estimates), pipelined execution through ``JoinQueryService`` with
per-stage scheme/algorithm planning and build-side cache reuse — verified
against the pure-NumPy reference join.

    PYTHONPATH=src python examples/query_pipeline.py [--fact-rows 65536]
"""
import argparse
import time

from repro.compile_cache import enable_compile_cache
from repro.core import CoProcessor
from repro.engine import JoinQueryService, QueryPlanner
from repro.queries import (JoinOrderOptimizer, PipelineExecutor,
                           make_star_query, reference_execute)


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--fact-rows", type=int, default=65536)
    ap.add_argument("--dim-rows", type=int, default=8192)
    ap.add_argument("--workers", type=int, default=2)
    args = ap.parse_args()

    cp = CoProcessor()
    print("calibrating unit costs on this host (paper §4.2)...")
    planner = QueryPlanner.calibrated(cp, n=16384, reps=2, delta=0.1)
    optimizer = JoinOrderOptimizer(planner)

    query = make_star_query(args.fact_rows, [args.dim_rows] * 3,
                            selectivities=[0.02, None, 0.5], seed=17,
                            aggregate=("count",))
    print(f"query: {query.describe()}\n")

    chosen = optimizer.optimize(query)
    worst = optimizer.worst_order(query)
    textual = optimizer.price_order(query, query.joins)
    print(chosen.describe())
    print(f"(textual order est {textual.est_total_s * 1e3:.2f} ms, "
          f"worst order est {worst.est_total_s * 1e3:.2f} ms)\n")

    ref_rows, ref_agg = reference_execute(query)
    svc = JoinQueryService(cp=cp, planner=planner, num_workers=args.workers)
    with PipelineExecutor(service=svc, optimizer=optimizer) as ex:
        res = ex.run(query, chosen)          # compile + warm the caches
        t0 = time.perf_counter()
        res = ex.run(query, chosen)
        elapsed = time.perf_counter() - t0
        hdr = (f"{'stage':<28} {'plan':<12} {'build':>7} {'probe':>7} "
               f"{'ms':>8} {'cache':<10}")
        print(hdr + "\n" + "-" * len(hdr))
        for s, o in zip(chosen.stages, res.outcomes):
            hit = ("table" if o.cache_hit else
                   "partition" if o.partition_cache_hit else "")
            print(f"{o.tag:<28} {o.plan.algorithm}/{o.plan.scheme:<8} "
                  f"{s.est_build:>7} {s.est_probe:>7} "
                  f"{o.wall_s * 1e3:>8.1f} {hit:<10}")
        st = svc.stats()

    got_rows, got_agg = res.rows_array(), res.aggregate
    assert got_agg == ref_agg and (got_rows == ref_rows).all()
    print(f"\n{res.rows} result rows (count={got_agg}) verified against "
          f"the NumPy reference")
    print(f"pipeline wall: {elapsed * 1e3:.1f} ms "
          f"(optimizer estimated {chosen.est_total_s * 1e3:.2f} ms)")
    c = st["cache"]
    print(f"caches: {c['hits']} table hits, "
          f"{c['partition_hits']} partition-layout hits, "
          f"{c['bytes'] / 2**20:.1f} MiB resident")
    print(f"stage hand-off: device-resident (StageView rid-chains), "
          f"{st['host_bytes_moved']} intermediate bytes through the host")


if __name__ == "__main__":
    main()
