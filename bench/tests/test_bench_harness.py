"""Tests of the benchmark harness, on the CPU at tiny sizes.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

Nothing here touches a TPU: runs are driven through ``run.run(...,
require_tpu=False)`` on copies of ``bench/`` whose configurations are cut
to a few thousand rows, and the one test of the chip check runs the real
entry point in a child process held to the CPU.
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import control  # noqa: E402
import run  # noqa: E402
import trace_reduce as tr  # noqa: E402

TINY_JOIN = {"build_tuples": 4096, "probe_tuples": 4096, "key_range": 4096}
TINY_SSB = {"lineorder": 100_000, "part": 2000, "supplier": 100,
            "date": 2556}


def _write(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f)


@pytest.fixture
def tiny_root(tmp_path):
    """A copy of ``bench/`` beside the program's ``src``, plus tiny cells
    added as files only: ``tiny_join`` and ``tiny_ssb``."""
    root = str(tmp_path / "bench")
    shutil.copytree(BENCH, root,
                    ignore=shutil.ignore_patterns("_cache", "tests",
                                                  "__pycache__"))
    os.symlink(os.path.join(REPO, "src"), str(tmp_path / "src"))
    cfg = run.load_json(os.path.join(root, "configs", "phj_paper_16m.json"))
    cfg["relations"].update(TINY_JOIN)
    _write(os.path.join(root, "configs", "tiny_join.json"), cfg)
    shutil.copy(os.path.join(root, "configs", "phj_paper_16m.py"),
                os.path.join(root, "configs", "tiny_join.py"))
    mix = run.load_json(os.path.join(root, "traffic", "uniform_cold.json"))
    mix["params"]["max_out"] = 8192
    _write(os.path.join(root, "traffic", "tiny_cold.json"), mix)
    _write(os.path.join(root, "workloads", "tiny_join.json"),
           {"config": "tiny_join", "traffic": "tiny_cold", "chips": 1,
            "why": "tiny"})
    cfg = run.load_json(os.path.join(root, "configs", "ssb_sf10.json"))
    cfg["tables"] = dict(TINY_SSB)
    _write(os.path.join(root, "configs", "tiny_ssb.json"), cfg)
    shutil.copy(os.path.join(root, "configs", "ssb_sf10.py"),
                os.path.join(root, "configs", "tiny_ssb.py"))
    _write(os.path.join(root, "workloads", "tiny_ssb.json"),
           {"config": "tiny_ssb", "traffic": "flight2_rotation",
            "chips": 1, "why": "tiny"})
    return root


def _run(root, cell, seed=5_000_000_017, seconds=1.0, trace=0):
    line = run.run(["--workload", cell, "--seed", str(seed), "--seconds",
                    str(seconds), "--trace", str(trace)], root=root,
                   require_tpu=False)
    return json.loads(line)


# ---------------------------------------------------------------------------
# Discovery by file name.
# ---------------------------------------------------------------------------

def test_discovery_finds_every_part_by_name():
    found = run.discover()
    assert {"phj_paper_16m", "ssb_sf10"} <= set(found["configs"])
    assert {"paper16m_uniform", "ssb_sf10_flight2"} <= set(found["workloads"])
    assert {"uniform_cold", "flight2_rotation"} <= set(found["traffic"])
    assert {"paper_join", "ssb_flight2"} <= set(found["kinds"])
    assert {"device_idle_share", "compiles_in_window", "plan_ms",
            "coprocess_ms", "ledger_mib_per_query"} <= set(found["metrics"])
    for cell in ("paper16m_uniform", "ssb_sf10_flight2"):
        spec = run.load_cell(cell)
        assert spec.workload["chips"] == 1
        assert hasattr(spec.kind, "Cell") and spec.kind.LIMITS
    assert set(run.load_metrics()) == set(found["metrics"])


def test_dropped_in_files_are_found(tiny_root):
    found = run.discover(tiny_root)
    assert "tiny_join" in found["workloads"]
    assert "tiny_cold" in found["traffic"]
    spec = run.load_cell("tiny_join", tiny_root)
    assert spec.config["relations"]["build_tuples"] == 4096
    with open(os.path.join(tiny_root, "metrics", "always_one.py"), "w") as f:
        f.write("UNIT = 'count'\n\ndef read(r):\n    return 1\n")
    assert "always_one" in run.discover(tiny_root)["metrics"]
    from compile_clock import CompileCounts
    empty = run.Readings("c", 0, 1.0, [], CompileCounts(), 0.0, 0, None, {})
    assert run.read_metrics(run.load_metrics(tiny_root), empty)[
        "always_one"] == {"value": 1.0, "unit": "count"}


def test_a_missing_file_is_an_error(tiny_root):
    with pytest.raises(run.BenchError):
        run.load_cell("no_such_cell", tiny_root)
    with pytest.raises(run.BenchError):
        run.load_cell("../workloads/tiny_join", tiny_root)


# ---------------------------------------------------------------------------
# Traffic generators.
# ---------------------------------------------------------------------------

def test_join_generator_is_seeded_and_reproducible():
    import jax

    kind = run.load_cell("paper16m_uniform").kind
    gen = jax.jit(lambda w, i: kind.generate(w, i, n_build=1024,
                                             n_probe=2048, key_range=512))
    seed = 2**33 + 7                        # past 32 bits
    w = kind.seed_words(seed, kind.WINDOW)
    a = [np.asarray(x) for x in gen(w, np.uint32(3))]
    b = [np.asarray(x) for x in gen(kind.seed_words(seed, kind.WINDOW),
                                    np.uint32(3))]
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert a[0].shape == (1024,) and a[1].shape == (2048,)
    assert a[0].min() >= 0 and a[0].max() < 512
    other = [np.asarray(x) for x in gen(w, np.uint32(4))]
    assert not np.array_equal(a[0], other[0])
    warm = [np.asarray(x) for x in gen(kind.seed_words(seed, kind.WARMUP),
                                       np.uint32(3))]
    assert not np.array_equal(a[0], warm[0])


def test_ssb_generator_cardinalities_and_selectivities():
    spec = run.load_cell("ssb_sf10_flight2")
    kind = spec.kind
    cfg = dict(spec.config, tables={"lineorder": 400_000, "part": 40_000,
                                    "supplier": 5_000, "date": 2556})
    t = kind.generate_tables(cfg, 99)
    t2 = kind.generate_tables(cfg, 99)
    assert all(np.array_equal(t[n][c], t2[n][c]) for n in t for c in t[n])
    for name, rows in cfg["tables"].items():
        assert {v.shape[0] for v in t[name].values()} == {rows}
        assert all(v.dtype == np.int32 for v in t[name].values())
    assert spec.config["tables"] == {"lineorder": 60_000_000,
                                     "part": 800_000, "supplier": 20_000,
                                     "date": 2556}
    assert t["date"]["d_datekey"].min() == 19920101
    assert t["date"]["d_datekey"].max() == 19981230
    # Another seed: the same data set, every table in another row order.
    t3 = kind.generate_tables(cfg, 100)
    for n in t:
        a, b = t[n], t3[n]
        first = next(iter(a))
        assert not np.array_equal(a[first], b[first])
        oa, ob = np.lexsort(tuple(a.values())), np.lexsort(tuple(b.values()))
        assert all(np.array_equal(a[c][oa], b[c][ob]) for c in a)
    lo = t["lineorder"]
    assert lo["lo_orderdate"].max() <= 19980802
    assert lo["lo_partkey"].min() >= 1 and lo["lo_partkey"].max() <= 40_000
    assert lo["lo_revenue"].min() > 0
    q21 = spec.mix["params"]["rotation"][0]
    part = t["part"]
    sel_p = np.mean((part["p_category"] >= q21["part"][1])
                    & (part["p_category"] < q21["part"][2]))
    sel_s = np.mean(t["supplier"]["s_region"] == q21["supplier"][1])
    assert abs(sel_p - 1 / 25) < 0.005 and abs(sel_s - 1 / 5) < 0.02
    q22 = spec.mix["params"]["rotation"][1]
    sel_b = np.mean((part["p_brand1"] >= q22["part"][1])
                    & (part["p_brand1"] < q22["part"][2]))
    assert abs(sel_b - 1 / 125) < 0.002


# SSB rev. 3, section 3.3: flight 2's predicates, as the spec writes them.
FLIGHT2_SPEC = {
    "Q2.1": (["MFGR#12"], "AMERICA"),
    "Q2.2": ([f"MFGR#22{b}" for b in range(21, 29)], "ASIA"),
    "Q2.3": (["MFGR#2239"], "EUROPE"),
}
REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]


def _decode(column: str, code: int) -> str:
    """An encoded part or supplier value back to the spec's string."""
    if column == "s_region":
        return REGIONS[code]
    category, brand = ((code, None) if column == "p_category"
                       else divmod(code, 40))
    m, c = divmod(category, 5)
    return f"MFGR#{m + 1}{c + 1}" + ("" if brand is None else
                                     str(brand + 1))


def test_flight2_templates_are_the_spec_predicates():
    rotation = run.load_cell("ssb_sf10_flight2").mix["params"]["rotation"]
    assert [t["name"] for t in rotation] == list(FLIGHT2_SPEC)
    for t in rotation:
        parts, region = FLIGHT2_SPEC[t["name"]]
        col, lo, hi = t["part"]
        assert [_decode(col, v) for v in range(lo, hi)] == parts
        col, lo, hi = t["supplier"]
        assert [_decode(col, v) for v in range(lo, hi)] == [region]


def test_ssb_warmup_rows_are_not_the_window_rows(tiny_root, monkeypatch):
    """Warm-up runs the rotation over another row order than the window's,
    so the window's fingerprints and filtered dimensions are new to the
    service; the window runs over the rows in ``--seed``'s order."""
    seen = []
    load_cell = run.load_cell

    def spying(name, root):
        spec = load_cell(name, root)
        prepare = spec.kind.Cell.prepare

        def spy(self, index):
            seen.append(self.tables["lineorder"]["lo_partkey"].copy())
            return prepare(self, index)

        monkeypatch.setattr(spec.kind.Cell, "prepare", spy)
        return spec

    monkeypatch.setattr(run, "load_cell", spying)
    seed = 5_000_000_021
    out = _run(tiny_root, "tiny_ssb", seed=seed)
    assert out["correct"] is True
    spec = load_cell("tiny_ssb", tiny_root)
    window = spec.kind.generate_tables(spec.config, seed)
    warm = spec.kind.generate_tables(spec.config, spec.kind.WARMUP_SEED,
                                     spec.kind.WARMUP)
    n = len(spec.mix["params"]["rotation"])
    assert len(seen) > n
    want = window["lineorder"]["lo_partkey"]
    assert all(np.array_equal(s, warm["lineorder"]["lo_partkey"])
               for s in seen[:n])
    assert all(np.array_equal(s, want) for s in seen[n:])
    assert not np.array_equal(seen[0], want)


# ---------------------------------------------------------------------------
# The window.
# ---------------------------------------------------------------------------

class _FakeCell:
    def __init__(self, times):
        self.times = list(times)

    def prepare(self, i):
        return i

    def execute(self, i):
        import time
        time.sleep(self.times[i % len(self.times)])
        return i


def test_window_finishes_the_query_in_flight():
    t_start, recs = run.run_window(_FakeCell([0.3]), 0.1, clients=1)
    assert len(recs) == 1 and recs[0].t_done - t_start >= 0.3
    t_start, recs = run.run_window(_FakeCell([0.02, 0.05]), 0.3, clients=2)
    assert len(recs) >= 6
    assert max(r.t_submit for r in recs) - t_start < 0.3
    assert sorted(r.index for r in recs) == list(range(len(recs)))


def test_window_arithmetic():
    recs = [run.Record(0, 10.0, 11.0), run.Record(1, 11.0, 14.0),
            run.Record(2, 14.0, 14.5), run.Record(3, 14.5, 16.0, error="x")]
    st = run.window_stats(10.0, recs, lambda r: False)
    assert st["attempted"] == 4 and st["failed"] == 1
    assert st["completed"] == 3
    assert st["elapsed_s"] == pytest.approx(6.0)
    assert st["queries_per_s"] == pytest.approx(3 / 6.0)
    assert st["latency_p50_ms"] == pytest.approx(1000.0)
    st = run.window_stats(10.0, recs[:2], lambda r: r.index == 1)
    assert st["failed"] == 1 and st["latency_p50_ms"] == pytest.approx(1e3)


# ---------------------------------------------------------------------------
# Whole runs on the CPU: the last line, the checks, the faults.
# ---------------------------------------------------------------------------

RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device",
               "checks"]


@pytest.mark.parametrize("cell", ["tiny_join", "tiny_ssb"])
def test_sound_run_last_line(tiny_root, cell):
    out = _run(tiny_root, cell)
    assert list(out) == RESULT_KEYS
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 1
    assert set(out["metrics"]) == {"setup_s", "queries_per_s",
                                   "latency_p50_ms"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert set(out["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert all(c["value"] == 0 and c["limit"] == 0
               for c in out["checks"].values())


def test_traced_run_reports_per_layer_metrics(tiny_root):
    out = _run(tiny_root, "tiny_join", trace=1)
    assert out["correct"] is True
    m = out["metrics"]
    assert {"compiles_in_window", "plan_ms", "coprocess_ms",
            "ledger_mib_per_query"} <= set(m)
    assert "setup_s" not in m
    # The CPU trace has no accelerator plane: nothing to read.
    assert "device_idle_share" not in m


def _halve_probe(q):
    from repro.core import Relation
    from repro.engine import JoinQuery

    if isinstance(q, JoinQuery):
        n = q.probe.size
        key = q.probe.key.at[n // 2:].set(-3)   # the pad key: no match
        q.probe = Relation(q.probe.rid, key)
    return q


def _alter_answer(outcome):
    from repro.core.hash_table import JoinResult

    r = outcome.result
    if isinstance(r, JoinResult) and int(r.count) > 1:
        outcome.result = JoinResult(r.probe_rid.at[0].set(r.probe_rid[1]),
                                    r.build_rid, r.count)
    return outcome


@pytest.mark.parametrize("cell", ["tiny_join", "tiny_ssb"])
@pytest.mark.parametrize("fault", ["half_batch", "altered_answer"])
def test_a_broken_timed_path_is_not_correct(tiny_root, monkeypatch, cell,
                                            fault):
    """Skip the chip check, break the path underneath the window, and see
    ``correct`` come out false: half of each batch left out, or one answer
    altered where it is produced."""
    from repro.engine import JoinQueryService

    submit = JoinQueryService.submit

    def broken(self, q, **kw):
        if fault == "half_batch":
            return submit(self, _halve_probe(q), **kw)
        wait = submit(self, q, **kw)
        return lambda *a, **k: _alter_answer(wait(*a, **k))

    monkeypatch.setattr(JoinQueryService, "submit", broken)
    out = _run(tiny_root, cell)
    assert out["correct"] is False
    assert any(c["value"] > c["limit"] for c in out["checks"].values())


def test_run_refuses_a_machine_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(BENCH, "run.py"),
                        "--workload", "paper16m_uniform", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == "" or not p.stdout.strip().splitlines()[
        -1].startswith("{")
    assert "no TPU" in p.stderr


def test_run_refuses_a_checkout_without_the_program(tmp_path):
    root = str(tmp_path / "bench")
    shutil.copytree(BENCH, root, ignore=shutil.ignore_patterns(
        "_cache", "tests", "__pycache__"))
    p = subprocess.run([sys.executable, os.path.join(root, "run.py"),
                        "--workload", "paper16m_uniform", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and "{" not in p.stdout


# ---------------------------------------------------------------------------
# Controls: the comparison refuses the step below the guarantee.
# ---------------------------------------------------------------------------

def test_join_control_is_not_correct(tiny_root):
    spec = run.load_cell("tiny_join", tiny_root)
    run.import_program(tiny_root)
    import jax

    for seed in (1, 2, 3):
        ctx = run.Context("tiny_join", seed, spec.mix["params"],
                          spec.config, spec.reference, jax.devices()[:1],
                          tiny_root)
        got = dict(spec.kind.control_checks(ctx))
        assert got["pairs_extra"] > spec.kind.LIMITS["pairs_extra"]


def test_ssb_control_is_not_correct():
    """The int32 sum (the program's ``wrap32`` path, here the reference
    computed at that precision) wraps once a group's revenue passes 2^31;
    at 5M lineorder rows one brand's yearly groups already do."""
    spec = run.load_cell("ssb_sf10_flight2")
    cfg = dict(spec.config, tables={"lineorder": 5_000_000,
                                    "part": 20_000, "supplier": 1000,
                                    "date": 2556})
    template = {"name": "one brand", "part": ["p_brand1", 278, 279],
                "supplier": ["s_region", 0, 5]}
    for seed in (1, 2, 3):
        t = spec.kind.generate_tables(cfg, seed)
        want, joined = spec.reference.flight2(t, template)
        low, _ = spec.reference.flight2(t, template, wrap32=True)
        assert want[:, 2].max() >= 2**31
        assert spec.reference.compare(low, want) > 0


def test_control_script_runs_the_program_path(tiny_root):
    """``control.py`` on the SSB cell drives the program's own int32
    path; at a tiny size no sum wraps, so the control reads 0 there."""
    assert control.main(["--workload", "tiny_ssb", "--seeds", "4",
                         "--seconds", "0.5"], root=tiny_root,
                        require_tpu=False) == 1


# ---------------------------------------------------------------------------
# Trace reduction on a synthetic trace.
# ---------------------------------------------------------------------------

def _ev(name, start, dur):
    return (name, start, dur, {})


def _synthetic_trace():
    host = {"name": "/host:CPU", "lines": [
        {"name": "main", "events": [
            _ev("bench.window", 1000, 10_000),
            _ev("bench.prepare", 1000, 2000),
            _ev("bench.wait", 3000, 8000),
            _ev("backend_compile", 6000, 2500),
            _ev("PjitFunction(step)", 5500, 4000)]}]}
    dev = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [
            _ev("fusion.1", 500, 1000),      # starts before the window
            _ev("seg_agg", 3000, 1000),
            _ev("fusion.1", 3500, 1500),     # overlaps seg_agg
            _ev("fusion.2", 9000, 1000)]},
        {"name": "XLA Modules", "events": [_ev("jit_step", 0, 20_000)]}]}
    other = {"name": "/device:TPU:1", "lines": [
        {"name": "XLA Ops", "events": [_ev("fusion.9", 1000, 10_000)]}]}
    return [host, dev, other]


def test_trace_reduction_busy_idle_kernels_and_gaps():
    red = tr.reduce_trace(_synthetic_trace(), device_ids=[0])
    # Busy: [1000,1500] + [3000,5000] + [9000,10000] = 3500 of 10000 ns.
    assert red["window_s"] == pytest.approx(10_000 / 1e9)
    assert red["busy_s"] == pytest.approx(3500 / 1e9)
    assert red["idle_share"] == pytest.approx(0.65)
    assert tr.kernel_seconds(red, lambda n: n == "seg_agg") == \
        pytest.approx(1000 / 1e9)
    assert tr.kernel_seconds(red, lambda n: n == "absent") is None
    assert red["op_s"]["fusion.1"] == pytest.approx(2000 / 1e9)
    assert red["device_ops"][0][0] == "fusion.1"
    gaps = dict((round(s * 1e9), n) for n, s in red["idle_gaps"])
    # Gaps: [1500,3000], [5000,9000], [10000,11000].
    assert set(gaps) == {1500, 4000, 1000}
    assert gaps[4000] == "bench.wait/backend_compile"
    assert gaps[1500] == "bench.prepare"
    assert [s for _, s in red["idle_gaps"]] == sorted(
        (s for _, s in red["idle_gaps"]), reverse=True)
    both = tr.reduce_trace(_synthetic_trace(), device_ids=[0, 1])
    assert both["busy_s"] == pytest.approx((3500 + 10_000) / 2 / 1e9)


def test_device_ops_are_named_by_instruction_and_shape():
    hlo = ("%while.5 = (s32[]{:T(128)}, s32[2097]{0:T(1024)}) while((s32[]"
           "{:T(128)}, s32[2097]{0:T(1024)}) %tuple.40), condition=%c")
    assert tr.op_name(hlo) == "while.5 (s32[], s32[2097])"
    assert tr.op_name("%fusion.3 = s32[8]{0:T(1024)} fusion(s32[8] %a)") \
        == "fusion.3 s32[8]"
    assert tr.op_name("plain") == "plain"


def test_trace_without_a_window_or_device_reads_nothing():
    host, dev, _ = _synthetic_trace()
    assert tr.reduce_trace([dev]) is None
    assert tr.reduce_trace([host]) is None
    assert tr.merge([(5, 7), (1, 3), (2, 4)]) == [(1, 4), (5, 7)]
    assert tr.gaps([(1, 4), (5, 7)], 0, 10) == [(0, 1), (4, 5), (7, 10)]


# ---------------------------------------------------------------------------
# Per-layer metric readers.
# ---------------------------------------------------------------------------

def test_metric_readers():
    from compile_clock import CompileCounts

    r = run.Readings(cell="c", completed=4, elapsed_s=8.0,
                     layers=[{"coprocess_s": 0.5}, {"coprocess_s": 1.5}],
                     compiles=CompileCounts(programs=3, cache_hits=1),
                     plan_s=0.002, ledger_bytes=8 << 20,
                     trace={"idle_share": 0.25}, peaks={})
    got = run.read_metrics(run.load_metrics(), r)
    assert got["device_idle_share"] == {"value": 25.0, "unit": "%"}
    assert got["compiles_in_window"]["value"] == 3
    assert got["plan_ms"]["value"] == pytest.approx(0.5)
    assert got["coprocess_ms"]["value"] == pytest.approx(1000.0)
    assert got["ledger_mib_per_query"]["value"] == pytest.approx(2.0)
    empty = run.Readings("c", 0, 1.0, [], CompileCounts(), 0.0, 0, None, {})
    got = run.read_metrics(run.load_metrics(), empty)
    assert set(got) == {"compiles_in_window"}
