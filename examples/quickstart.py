"""Quickstart: hash-join co-processing in 40 lines.

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import (CoProcessor, join_oracle, series_model_from_costs,
                        uniform_relation, unique_relation, ICI_LINK)
from repro.core.calibrate import APU_CPU, APU_GPU
from repro.core.shj import PROBE_SERIES

enable_compile_cache()

# 1. Data: build side R (unique keys), probe side S.
R = unique_relation(100_000, seed=0)
S = uniform_relation(400_000, key_range=150_000, seed=1)

# 2. Pick workload ratios with the paper's cost model (Eqs. 1-5 + δ-sweep).
model = series_model_from_costs(PROBE_SERIES.steps, [S.size] * 4,
                                APU_CPU, APU_GPU, ICI_LINK)
ratios, est = model.optimize_pl(delta=0.05)
print("PL ratios per probe step:", np.round(ratios, 2), f"est={est*1e3:.1f}ms")

# 3. Execute fine-grained co-processing across the two device groups.
cp = CoProcessor()
result, timing = cp.shj(R, S, num_buckets=32_768, max_out=2 * S.size,
                        build_ratios=[0.0, 0.3, 0.5, 0.3],
                        probe_ratios=list(ratios), table_mode="shared")
print(f"joined: {int(result.count):,} pairs in {timing.wall_s*1e3:.0f}ms "
      f"(build {timing.phase_s['build']*1e3:.0f}ms / "
      f"probe {timing.phase_s['probe']*1e3:.0f}ms)")

# 4. Verify against the oracle.
expected = join_oracle(R, S)
assert (result.valid_pairs() == expected).all()
print("verified against sort-merge oracle ✓")
