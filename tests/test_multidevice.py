"""Integration tests that need multiple XLA host devices — run in
subprocesses so the main pytest process keeps its single device."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

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


# The service and pipeline correctness checks, rerun where C and G are two
# devices: there every SHJ and group-by scheme of the catalog is offered
# (on one device both groups are the same device, and the planner offers
# one single-group scheme).
TWO_GROUP_CHECKS = [
    "tests/test_engine.py::test_service_executes_mixed_workload_correctly",
    "tests/test_engine.py::test_service_threaded_run_matches_oracle",
    "tests/test_queries.py::test_star_pipeline_matches_reference",
    "tests/test_queries.py::test_chain_pipeline_matches_reference",
    "tests/test_queries.py::test_grouped_sink_consumes_view",
    "tests/test_ops.py::test_service_groupby_query",
    "tests/test_ops.py::test_groupby_query_through_service",
    "tests/test_ops.py::test_semi_anti_star_matches_reference",
    "tests/test_ops.py::test_coprocessed_groupby",
]

CODE_SHARED_PLANNER = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import json, jax, numpy as np
from repro.core import CoProcessor, join_oracle
from repro.engine import JoinQueryService, QueryPlanner, make_workload
devs = jax.devices()
assert len(devs) == 2
queries = make_workload("uniform", num_queries=4, base_tuples=8192, seed=3)
pl = QueryPlanner()
two = JoinQueryService(cp=CoProcessor(), planner=pl, num_workers=0)
one = JoinQueryService(cp=CoProcessor(c_devices=devs[:1],
                                      g_devices=devs[:1]),
                       planner=pl, num_workers=0)
alone = JoinQueryService(cp=CoProcessor(), planner=QueryPlanner(),
                         num_workers=0)
out = {"shared": [two.plan_scope.shared_groups,
                  one.plan_scope.shared_groups]}
for name, svc in (("one", one), ("two", two), ("alone", alone)):
    outs = svc.run(queries)
    out[name] = [f"{o.plan.algorithm}/{o.plan.scheme}" for o in outs]
    out[name + "_ok"] = all(
        np.array_equal(o.result.valid_pairs(), join_oracle(q.build, q.probe))
        for o, q in zip(outs, queries))
    svc.close()
print(json.dumps(out))
"""


def test_service_and_pipelines_on_two_groups():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "no:randomly", *TWO_GROUP_CHECKS],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-2000:]


def test_one_planner_plans_each_service_for_its_groups():
    """One planner behind a two-group and a one-device service: each
    service's plans are those of its own groups, and the two-group plans
    are those of a planner no one-device service touched."""
    out = _run(CODE_SHARED_PLANNER)
    assert out["shared"] == [False, True]
    assert out["one_ok"] and out["two_ok"] and out["alone_ok"]
    assert out["two"] == out["alone"]
    assert set(out["one"]) <= {"shj/GPU_ONLY", "phj/DD"}
    assert set(out["two"]) - {"shj/GPU_ONLY"}
