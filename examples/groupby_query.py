"""Declarative group-by with a semi-join filter, end to end.

A star query — fact table F against a filtered dimension (inner) and a
pure filter dimension (semi) — grouped on F's low-cardinality attribute
with a sum aggregate.  The optimizer prices every edge order through the
engine's cost model (semi filters schedule early: they shrink the
pipeline), each stage runs as one engine query, and the group-by sink is
one more engine submission.  The result is verified row/value-exact
against the pure-NumPy reference.

Run:  PYTHONPATH=src python examples/groupby_query.py
"""
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.engine import JoinQueryService, QueryPlanner
from repro.queries import (JoinOrderOptimizer, PipelineExecutor,
                           make_star_query, reference_execute)


def main():
    enable_compile_cache()
    query = make_star_query(
        1 << 15, [2048, 1024], selectivities=[0.2, 0.5], seed=7,
        join_kinds=["inner", "semi"], group_by=("F.g",),
        aggregate=("sum", "F.m"))
    print("query:", query.describe())

    svc = JoinQueryService(planner=QueryPlanner(delta=0.25), num_workers=2)
    optimizer = JoinOrderOptimizer(svc.planner)
    with PipelineExecutor(service=svc, optimizer=optimizer) as ex:
        physical, result = ex.run_optimized(query)
        print(physical.describe())
        print(f"\n{result.rows} groups in {result.wall_s * 1e3:.1f} ms")
        for o in result.outcomes:
            d = o.to_dict()
            print(f"  {d['tag']:28s} {d['algorithm']}/{d['scheme']:9s} "
                  f"kind={d['kind']:6s} wall={d['wall_s'] * 1e3:7.1f} ms")

        ref_rows, _ = reference_execute(query)
        got = result.rows_array()
        assert got.shape == ref_rows.shape and (got == ref_rows).all()
        print("verified: exact match against the NumPy reference")
        top = np.argsort(got[:, -1])[-3:][::-1]
        print("top groups by sum(F.m):")
        for i in top:
            print(f"  F.g={int(got[i, 0]):3d}  sum={int(got[i, -1])}")


if __name__ == "__main__":
    main()
