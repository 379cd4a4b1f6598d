"""Integration tests that need multiple XLA host devices — run in
subprocesses so the main pytest process keeps its single device."""
import json
import subprocess
import sys

import pytest

CODE_COPROC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, jax
from repro.core import (CoProcessor, PCIE_LINK, join_oracle,
                        uniform_relation, unique_relation)
b = unique_relation(4096, seed=1)
p = uniform_relation(8192, key_range=6000, seed=2)
exp = join_oracle(b, p)
out = {}
cp = CoProcessor()
assert cp.c.size == 2 and cp.g.size == 6
for mode in ("shared", "separate"):
    res, t = cp.shj(b, p, num_buckets=1024, max_out=65536,
                    build_ratios=[0.25]*4, probe_ratios=[0.5]*4,
                    table_mode=mode)
    got = res.valid_pairs()
    out[mode] = bool(got.shape == exp.shape and (got == exp).all())
cpd = CoProcessor(link=PCIE_LINK, discrete=True)
res, t = cpd.shj(b, p, num_buckets=1024, max_out=65536,
                 build_ratios=[0.25]*4, probe_ratios=[0.5]*4,
                 table_mode="separate")
out["discrete"] = bool((res.valid_pairs() == exp).all())
out["discrete_transfer_bytes"] = int(t.transfer_bytes)
print(json.dumps(out))
"""

CODE_DRYRUN = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, dataclasses, jax, numpy as np
from repro.configs import all_configs, reduced, SHAPES, ShapeSpec
from repro.launch import dryrun as dr
from repro.launch.mesh import make_auto_mesh
mesh = make_auto_mesh((2, 4), ("data", "model"))
cfg = reduced(all_configs()["qwen3_8b"])
cfg = dataclasses.replace(cfg, d_model=64, num_heads=8, num_kv_heads=4,
                          head_dim=16, d_ff=128)
shape = ShapeSpec("t", 64, 8, "train")
dr.SHAPES["t"] = shape
lowered = dr._build_lowered(cfg, shape, mesh, None, "float32")
compiled = lowered.compile()
cost = dr.cost_analysis_dict(compiled)
colls = dr.parse_collectives(compiled.as_text())
print(json.dumps({"flops": cost.get("flops", 0.0),
                  "collectives": len(colls),
                  "ok": True}))
"""


def _run(code: str) -> dict:
    # JAX_PLATFORMS=cpu: these tests are about forced HOST devices; without
    # it, a machine with libtpu installed but no TPU blocks in backend init.
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "HOME": "/root", "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_coprocessor_real_two_groups():
    out = _run(CODE_COPROC)
    assert out["shared"] and out["separate"] and out["discrete"]
    assert out["discrete_transfer_bytes"] > 0


@pytest.mark.slow
def test_dryrun_machinery_small_mesh():
    out = _run(CODE_DRYRUN)
    assert out["ok"] and out["flops"] > 0 and out["collectives"] > 0
