"""End-to-end driver: the paper's full experiment pipeline at scale.

Runs SHJ and PHJ under every co-processing scheme (CPU-only, OL, DD, PL,
BasicUnit) on uniform and skewed data, with cost-model-chosen knobs, and
verifies every result against the oracle.

    PYTHONPATH=src python examples/coprocess_join.py [--tuples 1000000]
"""
import argparse
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import (CoProcessor, join_oracle, series_model_from_costs,
                        skewed_relation, uniform_relation, ICI_LINK)
from repro.core.calibrate import APU_CPU, APU_GPU
from repro.core.shj import BUILD_SERIES, PROBE_SERIES


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--tuples", type=int, default=250_000)
    args = ap.parse_args()
    n = args.tuples
    cp = CoProcessor()

    for skew, gen in (("uniform", uniform_relation),
                      ("high-skew", lambda m, seed: skewed_relation(
                          m, s_percent=25, seed=seed))):
        r = gen(n, seed=1)
        s = gen(n, seed=2)
        exp = join_oracle(r, s)
        print(f"\n== {skew}: |R|=|S|={n:,}, matches={len(exp):,} ==")

        # Cost-model-chosen PL ratios per phase (the paper's automaticity).
        rb, _ = series_model_from_costs(
            BUILD_SERIES.steps, [n] * 4, APU_CPU, APU_GPU,
            ICI_LINK).optimize_pl(delta=0.05)
        rp, _ = series_model_from_costs(
            PROBE_SERIES.steps, [n] * 4, APU_CPU, APU_GPU,
            ICI_LINK).optimize_pl(delta=0.05)

        nb = max(1024, n // 4)
        mo = 2 * n + len(exp)
        plans = {
            "CPU-only": ([1.0] * 4, [1.0] * 4),
            "OL (GPU)": ([0.0] * 4, [0.0] * 4),
            "DD": ([0.25] * 4, [0.42] * 4),
            "PL (model)": (list(rb), list(rp)),
        }
        for name, (br, pr) in plans.items():
            res, t = cp.shj(r, s, num_buckets=nb, max_out=mo,
                            build_ratios=br, probe_ratios=pr,
                            table_mode="shared")
            ok = (res.valid_pairs() == exp).all()
            print(f"  SHJ {name:11s} {t.wall_s*1e3:8.0f}ms verified={ok}")
            assert ok
        res, t = cp.phj(r, s, shj_bits=2,  # planner picks the pass schedule
                        max_out=mo, partition_ratio=0.25, join_ratio=0.4)
        ok = (res.valid_pairs() == exp).all()
        print(f"  PHJ DD/PL     {t.wall_s*1e3:8.0f}ms verified={ok} "
              f"(partition {t.phase_s['partition']*1e3:.0f}ms)")
        assert ok
        res, t, ratios = cp.basic_unit_shj(r, s, num_buckets=nb, max_out=mo,
                                           chunk=max(4096, n // 16))
        ok = (res.valid_pairs() == exp).all()
        print(f"  BasicUnit     {t.wall_s*1e3:8.0f}ms verified={ok} "
              f"realized-ratios={ {k: round(v,2) for k,v in ratios.items()} }")
        assert ok
    print("\nall schemes verified ✓")


if __name__ == "__main__":
    main()
