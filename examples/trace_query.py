"""Trace a 3-join star query and write a Perfetto-loadable trace.json.

Runs one multi-join star through ``PipelineExecutor`` with the service's
default ``Tracer`` on, then exports the recorded query lifecycle —
admit -> queue -> plan -> partition/build -> probe/join -> gather ->
finalize — as Chrome trace-event JSON.  Open https://ui.perfetto.dev and
drag ``trace.json`` in to see the worker tracks, the async queue-wait
lane, and every span's attributes (tenant, scheme, q_key).

Also prints the predicted-vs-measured cost-model audit: per-phase
prediction-error ratios (measured/estimated, p50/p95) from the same run.

    PYTHONPATH=src python examples/trace_query.py [--out trace.json]
"""
import argparse

from repro.compile_cache import enable_compile_cache
from repro.core import CoProcessor
from repro.engine import JoinQueryService, QueryPlanner
from repro.queries import (JoinOrderOptimizer, PipelineExecutor,
                           make_star_query, reference_execute)


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="trace.json")
    ap.add_argument("--fact-rows", type=int, default=65536)
    ap.add_argument("--dim-rows", type=int, default=8192)
    args = ap.parse_args()

    cp = CoProcessor()
    print("calibrating unit costs on this host (paper §4.2)...")
    planner = QueryPlanner.calibrated(cp, n=16384, reps=1, delta=0.25)
    optimizer = JoinOrderOptimizer(planner)

    query = make_star_query(args.fact_rows, [args.dim_rows] * 3,
                            selectivities=[0.02, None, 0.5], seed=17,
                            aggregate=("count",))
    print(f"query: {query.describe()}\n")
    ref_rows, ref_agg = reference_execute(query)

    svc = JoinQueryService(cp=cp, planner=planner, num_workers=2)
    chosen = optimizer.optimize(query)
    with PipelineExecutor(service=svc, optimizer=optimizer) as ex:
        ex.run(query, chosen)               # warm: compiles land here
        svc.tracer.clear()                  # keep only the traced run
        res = ex.run(query, chosen, tenant="demo")
        st = svc.stats()

    assert res.aggregate == ref_agg and (res.rows_array() == ref_rows).all()
    path = svc.tracer.write_chrome_trace(args.out)
    spans = svc.tracer.spans()
    print(f"{len(spans)} spans from {len(res.outcomes)} stages "
          f"-> {path}  (load it at https://ui.perfetto.dev)")

    # Per-stage structured traces ride on every outcome too.
    for o in res.outcomes:
        phases = ", ".join(
            f"{d['name']}={d['dur_s'] * 1e3:.1f}ms" for d in o.trace
            if d["name"] in ("partition", "build", "probe", "join"))
        print(f"  {o.tag:<28} {o.plan.algorithm}/{o.plan.scheme:<8} "
              f"{phases}")

    audit = st["metrics"]["prediction_error"]
    print(f"\ncost-model audit: {audit['count']} phase executions")
    for phase, s in sorted(audit["phases"].items()):
        print(f"  {phase:<10} measured/est p50={s['p50']:.2f} "
              f"p95={s['p95']:.2f}  (n={s['count']})")


if __name__ == "__main__":
    main()
