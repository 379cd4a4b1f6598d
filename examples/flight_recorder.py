"""Trigger the flight recorder and read its post-mortem dump.

Drives a burst of deadline-doomed queries at ``JoinQueryService`` so the
admission layer sheds a storm of them, which trips the flight recorder's
shed-storm trigger; then injects one failing pipeline stage, which
always dumps.  Prints where each dump landed and a digest of the last
bundle — the recent query lifecycles (outcome summaries, admission
decisions, the failure) a post-mortem starts from.

    PYTHONPATH=src python examples/flight_recorder.py [--out-dir dumps]
"""
import argparse
import json
import os

from repro.compile_cache import enable_compile_cache
from repro.core import CoProcessor, uniform_relation, unique_relation
from repro.engine import (Backpressure, JoinQuery, JoinQueryService,
                          QueryPlanner, Tenant)
from repro.obs import FlightRecorder, validate_dump


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--out-dir", default="dumps")
    ap.add_argument("--rows", type=int, default=16384)
    args = ap.parse_args()
    os.makedirs(args.out_dir, exist_ok=True)

    cp = CoProcessor()
    planner = QueryPlanner(delta=0.25)
    # A recorder that writes dumps straight to disk, with a small storm
    # threshold and no cooldown so the demo fires quickly.
    flight = FlightRecorder(name="demo", storm_n=4, storm_window_s=10.0,
                            min_dump_gap_s=0.0, dump_dir=args.out_dir)
    svc = JoinQueryService(cp=cp, planner=planner, num_workers=2,
                           tenants=[Tenant("gold", deadline_s=30.0)],
                           flight=flight)
    with svc:
        # 1) Normal traffic: lifecycles land in the ring.
        for i in range(4):
            b = unique_relation(args.rows, seed=i)
            s = uniform_relation(args.rows, key_range=args.rows,
                                 seed=100 + i)
            svc.submit(JoinQuery(build=b, probe=s, query_id=i,
                                 tenant="gold"))()
        print(f"recorded {len(svc.flight)} lifecycle records")

        # 2) A shed storm: impossible deadlines -> admission sheds them
        #    back-to-back, tripping the storm trigger.
        svc._admission_estimate = lambda q: (60.0, 0.5)
        svc._degraded_estimate = lambda q: None
        shed = 0
        for i in range(8):
            b = unique_relation(256, seed=i)
            s = uniform_relation(256, key_range=256, seed=i + 1)
            try:
                svc.submit(JoinQuery(build=b, probe=s, query_id=100 + i,
                                     tenant="gold"), block=False)
            except Backpressure:
                shed += 1
        print(f"shed {shed} queries -> storm dump(s): "
              f"{[os.path.basename(p) for p in svc.flight.dump_paths]}")

        # 3) A failing stage: always dumps.
        svc._admission_estimate = lambda q: (1e-3, 0.5)
        handle = svc.submit_deferred(
            lambda outs: (_ for _ in ()).throw(RuntimeError("stage bug")),
            tenant="gold")
        try:
            handle()
        except RuntimeError:
            pass

    paths = svc.flight.dump_paths
    print(f"{len(paths)} dump(s) in {args.out_dir}/")
    with open(paths[-1]) as f:
        bundle = json.load(f)
    assert validate_dump(bundle), "dump failed schema validation"
    print(f"last dump: reason={bundle['reason']!r}, "
          f"counts={bundle['counts']}, tenants={list(bundle['tenants'])}")
    for rec in bundle["records"][-5:]:
        kind = rec["kind"]
        if kind == "outcome":
            print(f"  t={rec['t']:.3f} outcome q{rec['query_id']} "
                  f"{rec['algorithm']}/{rec['scheme']} "
                  f"wall={rec['wall_s']:.4f}s")
        elif kind == "admission":
            print(f"  t={rec['t']:.3f} admission {rec['action']} "
                  f"q{rec.get('query_id')} ({rec.get('reason')})")
        else:
            print(f"  t={rec['t']:.3f} FAILURE {rec.get('where')}: "
                  f"{rec.get('error')}")


if __name__ == "__main__":
    main()
