"""The paper's technique inside the LM stack: MoE expert dispatch as
radix partitioning (n1/n2/n3), vs. the dense one-hot dispatch.

    PYTHONPATH=src python examples/moe_dispatch.py
"""
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import MoECfg, all_configs, reduced
from repro.layers.moe import moe_dense, moe_sorted, moe_specs
from repro.models.params import materialize

enable_compile_cache()

cfg = reduced(all_configs()["granite_moe_3b"])
cfg = dataclasses.replace(
    cfg, moe=MoECfg(num_experts=16, top_k=4, d_ff=64, capacity_factor=1.5,
                    group_size=4096))
params = materialize(moe_specs(cfg), jax.random.PRNGKey(0), jnp.float32)
x = jax.random.normal(jax.random.PRNGKey(1), (8, 512, cfg.d_model))

f_dense = jax.jit(lambda p, x: moe_dense(p, cfg, x))
f_sorted = jax.jit(lambda p, x: moe_sorted(p, cfg, x))
y1, aux1 = jax.block_until_ready(f_dense(params, x))
y2, aux2 = jax.block_until_ready(f_sorted(params, x))
np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=3e-5)
print("dense one-hot dispatch == radix-partition dispatch ✓",
      f"(aux load-balance loss {float(aux1):.3f})")

for name, f in (("dense", f_dense), ("sorted(n1-n3)", f_sorted)):
    t0 = time.perf_counter()
    for _ in range(5):
        jax.block_until_ready(f(params, x))
    print(f"  {name:14s} {(time.perf_counter()-t0)/5*1e3:7.1f} ms/call")
print("\nThe 'sorted' path routes tokens with repro.core.partition --")
print("the same n1 (expert id) / n2 (histogram+scan) / n3 (scatter) steps")
print("the paper defines for radix hash-join partitioning.")
