import os
# Benchmarks need real two-group co-processing.  The device-group layout is
# env-configurable: REPRO_NUM_DEVICES host devices total (default 8), of
# which REPRO_C_DEVICES form the C-group (default 2; consumed by
# CoProcessor).  (Deliberately NOT 512 — that flag belongs only to
# launch/dryrun.py.)
NUM_DEVICES = int(os.environ.get("REPRO_NUM_DEVICES", "8"))
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    # Append rather than setdefault: a user's unrelated XLA_FLAGS must not
    # silently swallow the requested device-group layout.  An explicit
    # count in XLA_FLAGS wins over REPRO_NUM_DEVICES.
    os.environ["XLA_FLAGS"] = (_flags + " " if _flags else "") + \
        f"--xla_force_host_platform_device_count={NUM_DEVICES}"
"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV; per-figure artifacts land in
reports/bench/<name>.json and every invocation writes a machine-readable
rollup reports/bench/BENCH_<utc-stamp>.json (the perf trajectory).

  python -m benchmarks.run            # full suite
  python -m benchmarks.run --only fig4,roofline
  python -m benchmarks.run --only engine_throughput --smoke
  REPRO_BENCH_SCALE=16 ...            # paper-scale 16M-tuple relations
  REPRO_NUM_DEVICES=4 REPRO_C_DEVICES=1 ...  # device-group layout
"""
import argparse
import sys
import time
import traceback


def registry(smoke: bool = False):
    from functools import partial

    from . import (alloc_figs, engine_bench, groupby_bench, paper_figs,
                   query_bench, roofline, scale_figs, slo_bench)
    return {
        "fig3": paper_figs.fig3_time_breakdown,
        "fig4": paper_figs.fig4_step_unit_costs,
        "fig5_6": paper_figs.fig5_6_pl_ratios,
        "fig7": paper_figs.fig7_dd_estimate_vs_measured,
        "fig8": paper_figs.fig8_pl_special_case,
        "fig9": paper_figs.fig9_monte_carlo,
        "fig10": paper_figs.fig10_shared_vs_separate,
        "fig11_12": alloc_figs.fig11_12_allocator,
        "divergence": alloc_figs.workload_divergence,
        "partition_fused": paper_figs.partition_fused_bench,
        "table3": paper_figs.table3_step_granularity,
        "fig13_14_uniform": lambda: scale_figs.fig13_14_end_to_end("uniform"),
        "fig13_14_high_skew": lambda: scale_figs.fig13_14_end_to_end("high"),
        "fig15": paper_figs.fig15_selectivity,
        "fig16": paper_figs.fig16_basic_unit,
        "fig19": scale_figs.fig19_large_data,
        "fig20": alloc_figs.fig20_locking_microbench,
        "tpu_projection": scale_figs.tpu_pod_projection,
        "roofline": roofline.run,
        "engine_throughput": partial(engine_bench.engine_throughput,
                                     smoke=smoke),
        "query_pipeline": partial(query_bench.query_pipeline, smoke=smoke),
        "groupby": partial(groupby_bench.groupby_bench, smoke=smoke),
        "slo_bench": partial(slo_bench.slo_bench, smoke=smoke),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated benchmark names")
    ap.add_argument("--smoke", action="store_true",
                    help="small sizes/counts for CI (engine_throughput)")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    reg = registry(smoke=args.smoke)
    names = args.only.split(",") if args.only else list(reg)
    failures = 0
    results = {}
    for name in names:
        t0 = time.time()
        print(f"# === {name} ===", flush=True)
        try:
            payload = reg[name]()
            dt = time.time() - t0
            results[name] = {"ok": True, "seconds": dt,
                             "payload": payload if isinstance(payload, dict)
                             else None}
            print(f"# {name} done in {dt:.1f}s", flush=True)
        except Exception:
            failures += 1
            results[name] = {"ok": False, "seconds": time.time() - t0,
                             "error": traceback.format_exc(limit=5)}
            print(f"# {name} FAILED", flush=True)
            traceback.print_exc()
            # Post-mortem: dump every live flight recorder next to the
            # rollup so CI uploads the recent query lifecycles that led
            # up to the failure (FLIGHT_*.json — outside the BENCH_*
            # glob check_regression reads).
            try:
                from repro.obs import dump_live_recorders

                from .common import REPORT_DIR
                for p in dump_live_recorders(REPORT_DIR,
                                             reason=f"bench_{name}"):
                    print(f"# flight dump -> {p}", flush=True)
            except Exception:
                pass
    from .common import write_run_summary
    path = write_run_summary(results)
    print(f"# run summary -> {path}", flush=True)
    if failures:
        sys.exit(f"{failures} benchmarks failed")


if __name__ == '__main__':
    main()
