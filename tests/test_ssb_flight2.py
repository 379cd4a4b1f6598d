"""SSB flight 2 through the service on one device, where the C and G groups
are the same device as on one chip: exact answers against the benchmark's
NumPy reference, one plan per query whatever the calibration, the planner's
candidate sets, the group-by's host crossings and the ``plan_changes``
counter."""
from __future__ import annotations

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import CoProcessor
from repro.core.coprocess import _round_up
from repro.core.pass_planner import PassPlanner
from repro.core.relation import Relation, radix_of
from repro.core.shj import BUILD_SERIES, PROBE_SERIES
from repro.engine import JoinQueryService, PlanScope, QueryPlanner
from repro.obs import MetricsRegistry, TransferLedger
from repro.ops.groupby import groupby_coprocessed
from repro.queries import PipelineExecutor

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench")
SMALL = {"lineorder": 60_000, "part": 2_000, "supplier": 200, "date": 2556}


def _bench_module(rel_path: str, name: str):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, rel_path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


KIND = _bench_module("traffic/ssb_flight2.py", "ssb_test_kind")
REF = _bench_module("configs/ssb_sf10.py", "ssb_test_reference")
with open(os.path.join(BENCH, "traffic", "flight2_rotation.json")) as f:
    ROTATION = json.load(f)["params"]["rotation"]
with open(os.path.join(BENCH, "configs", "ssb_sf10.json")) as f:
    CONFIG = dict(json.load(f), tables=SMALL)


@pytest.fixture(scope="module")
def tables():
    return KIND.generate_tables(CONFIG, 2**31 + 11)


@pytest.fixture(scope="module")
def cp():
    """One CoProcessor for the module: its groups share the one device,
    and every service below reuses its compiled programs."""
    cp = CoProcessor()
    assert cp.c.devices == cp.g.devices
    return cp


def _calibration(scale: float = 1.0) -> dict:
    """A calibration under which the planner that prices C and G as two
    processors picks PHJ/DD for the 2^24 x 2^24 join, as on the chip."""
    steps = list(BUILD_SERIES.steps) + list(PROBE_SERIES.steps)
    return {"u_overrides": {s.name: (2e-9 * scale, 2e-9 * scale)
                            for s in steps},
            "partition": [2e-9, 2e-9, 2e-9],
            "handoff_latency_s": 2e-4, "handoff_bw_bytes_per_s": 3.937e9}


def _planner(c: dict, **kw) -> QueryPlanner:
    """Built as the benchmark harness builds its calibrated planner."""
    return QueryPlanner(
        u_overrides={k: tuple(v) for k, v in c["u_overrides"].items()},
        pass_planner=PassPlanner(*c["partition"]), partition_device_g=None,
        handoff_latency_s=c["handoff_latency_s"],
        handoff_bw_bytes_per_s=c["handoff_bw_bytes_per_s"], **kw)


SHARED = PlanScope(shared_groups=True)
DISTINCT = PlanScope(shared_groups=False)


def _run(svc, tables, template, *, wrap32=False):
    """(physical plan, result, answer rows, joined rows) of one query."""
    ex = PipelineExecutor(service=svc)
    physical, res = ex.run_optimized(
        KIND.make_query(tables, template, wrap32=wrap32))
    joined = int(res.outcomes[len(physical.stages) - 1].result.count)
    return physical, res, KIND.answer_rows(res.columns), joined


def _plan_lines(name, physical, res) -> list[str]:
    """The harness's ``plans:`` entries of one query, with its join order."""
    out = [f"{name} order: " + " > ".join(str(j) for j in physical.order)]
    for i, o in enumerate(res.outcomes):
        what = (str(physical.stages[i].join) if i < len(physical.stages)
                else "group-by")
        p = o.plan
        sched = f" schedule={list(p.schedule)}" if p.schedule else ""
        out.append(f"{name} {what}: {p.algorithm}/{p.scheme}{sched}")
    return out


# ---------------------------------------------------------------------------
# (a) Exact answers, int64 sums past 2^31.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k", range(len(ROTATION)),
                         ids=[t["name"] for t in ROTATION])
def test_flight2_matches_reference(cp, tables, k):
    svc = JoinQueryService(cp=cp, planner=_planner(_calibration()))
    try:
        physical, res, rows, joined = _run(svc, tables, ROTATION[k])
    finally:
        svc.close()
    want, want_joined = REF.flight2(tables, ROTATION[k])
    assert want.shape[0] > 0
    assert REF.compare(rows, want) == 0
    assert joined == want_joined
    assert [o.plan.scheme for o in res.outcomes] == ["GPU_ONLY"] * 4


def test_flight2_sums_past_int32_are_exact(cp, tables):
    """Revenue scaled so that group sums pass 2^31: the program's int64
    path equals the reference, its int32 path (``wrap32``) does not."""
    big = {n: dict(c) for n, c in tables.items()}
    rev = big["lineorder"]["lo_revenue"].astype(np.int64) * 200
    big["lineorder"]["lo_revenue"] = np.minimum(rev, 2**31 - 1).astype(
        np.int32)
    template = ROTATION[0]
    want, want_joined = REF.flight2(big, template)
    assert want[:, 2].max() >= 2**31
    svc = JoinQueryService(cp=cp, planner=_planner(_calibration()))
    try:
        _, _, rows, joined = _run(svc, big, template)
        _, _, rows32, _ = _run(svc, big, template, wrap32=True)
    finally:
        svc.close()
    assert REF.compare(rows, want) == 0 and joined == want_joined
    assert REF.compare(rows32, want) > 0


def test_scans_are_spanned(cp, tables):
    svc = JoinQueryService(cp=cp, planner=_planner(_calibration()))
    try:
        _run(svc, tables, ROTATION[2])
        spans = svc.tracer.spans()
    finally:
        svc.close()
    scans = {s.attrs["table"]: s.attrs for s in spans if s.name == "scan"}
    assert set(scans) == set(SMALL)
    part = tables["part"]["p_brand1"]
    lo, hi = ROTATION[2]["part"][1:]
    assert scans["part"]["rows_in"] == SMALL["part"]
    assert scans["part"]["rows_out"] == int(((part >= lo)
                                             & (part < hi)).sum())
    assert scans["lineorder"]["rows_out"] == SMALL["lineorder"]
    assert sum(s.name == "optimize" for s in spans) == 1


# ---------------------------------------------------------------------------
# (b) One plan per query, whatever the calibration and online scales.
# ---------------------------------------------------------------------------

def _flight_plan_lines(cp, tables, planner) -> list[str]:
    svc = JoinQueryService(cp=cp, planner=planner)
    lines = []
    try:
        for t in ROTATION:
            physical, res, _, _ = _run(svc, tables, t)
            lines += _plan_lines(t["name"], physical, res)
    finally:
        svc.close()
    return lines


def test_flight2_plans_do_not_follow_the_calibration(cp, tables):
    """Two machines' hand-off bandwidths, unit costs x0.5 and x2, online
    scales moved by a calibration tick: the same plan lines."""
    slow = _calibration(0.5)
    fast = dict(_calibration(2.0), handoff_bw_bytes_per_s=7.741e9)
    ticked = _planner(fast)
    v0 = ticked.online.version
    ticked.online.observe("shj_probe:GPU_ONLY", 1.0, 3.0)
    ticked.online.observe("phj_join", 1.0, 0.25)
    ticked.online.observe("groupby_agg:GPU_ONLY", 1.0, 0.2)
    assert ticked.online.version > v0
    runs = [_flight_plan_lines(cp, tables, p)
            for p in (_planner(slow), ticked)]
    assert runs[0] == runs[1]
    assert all(line.endswith("shj/GPU_ONLY")
               for line in runs[0] if ":" in line and "order" not in line
               and "group-by" not in line)
    assert sum(line.endswith("groupby/GPU_ONLY") for line in runs[0]) == 3


# Every join stage and group-by of the flight at SF10, as the optimizer
# estimates them: (build, probe) rows, then the group-by's rows.
SF10_STAGES = [(32_000, 60_000_000), (4_000, 2_400_000), (2556, 480_000),
               (6_400, 60_000_000), (4_000, 480_000), (2556, 96_000),
               (800, 60_000_000), (4_000, 60_000), (2556, 12_000)]
SF10_GROUPBY = [480_000, 96_000, 12_000]


def test_sf10_stage_plans_do_not_follow_the_calibration():
    """At SF10's sizes too: each stage and group-by gets one plan under
    unit costs x0.5 and x2 and after a calibration tick."""
    plans = []
    for scale, tick in ((0.5, False), (1.0, False), (2.0, False),
                        (2.0, True)):
        pl = _planner(_calibration(scale))
        if tick:
            pl.online.observe("shj_probe:GPU_ONLY", 1.0, 10.0)
            pl.online.observe("shj_build:GPU_ONLY", 1.0, 10.0)
        plans.append(
            [(p.algorithm, p.scheme) for p in
             (pl.choose(b, n, max_out=n, c_load=2.0, record=False,
                        scope=SHARED)
              for b, n in SF10_STAGES)]
            + [(g.scheme, g.schedule) for g in
               (pl.choose_groupby(n, g_load=2.0, record=False,
                                  scope=SHARED)
                for n in SF10_GROUPBY)])
    assert all(p == plans[0] for p in plans)
    assert set(plans[0]) == {("shj", "GPU_ONLY"), ("GPU_ONLY", None)}


# ---------------------------------------------------------------------------
# (c) The planner's candidates on shared and on distinct groups.
# ---------------------------------------------------------------------------

SIZES = [(32_000, 60_000_000), (4_000, 2_400_000), (2556, 480_000),
         (1 << 24, 1 << 24)]


@pytest.mark.parametrize("scope", [SHARED, DISTINCT],
                         ids=["shared", "distinct"])
def test_candidates_follow_the_groups(scope):
    pl = _planner(_calibration())
    shared = scope.shared_groups
    for b, p in SIZES:
        schemes = [c.scheme for c in
                   pl._shj_candidates(b, p, False, scope=scope)]
        assert schemes == (["GPU_ONLY"] if shared
                           else list(pl.allowed_schemes))
    gb = {n: pl.choose_groupby(n, record=False, scope=scope)
          for n in (4096, 1 << 24)}
    if shared:
        assert {(g.scheme, g.schedule) for g in gb.values()} == {
            ("GPU_ONLY", None)}
    else:
        assert gb[1 << 24].scheme == "DD"
    # A cache-resident build table: PHJ is not offered on shared groups
    # (its split halves the estimate, not the work) and SHJ is the plan;
    # on distinct groups PHJ stays a candidate.  Past the cache it is
    # offered on both.
    assert pl._offers_phj(32_000, "inner", scope) is not shared
    assert pl._offers_phj(1 << 24, "inner", scope)
    assert pl.choose(32_000, 60_000_000, max_out=1 << 22, record=False,
                     scope=scope).algorithm == "shj"


@pytest.mark.parametrize("allowed,offered", [
    (("DD",), ["DD"]),
    (("CPU_ONLY", "DD"), ["CPU_ONLY"]),
    (("GPU_ONLY", "CPU_ONLY", "DD"), ["GPU_ONLY"]),
], ids=["dd-only", "cpu-and-dd", "all"])
def test_shared_groups_rule_stays_within_allowed_schemes(allowed, offered):
    """The one-chip rule picks the single-group scheme among the allowed
    ones; a catalog without one keeps what it allows."""
    pl = _planner(_calibration(), allowed_schemes=allowed)
    got = [c.scheme for c in
           pl._shj_candidates(4_000, 480_000, False, scope=SHARED)]
    assert got == offered
    plan = pl.choose(4_000, 480_000, max_out=1 << 20, record=False,
                     scope=SHARED)
    assert plan.scheme == offered[0]
    gb = pl.choose_groupby(4096, record=False, scope=SHARED)
    assert gb.scheme == offered[0]


def test_one_planner_prices_each_scope_for_its_own_groups():
    """A planner shared by a one-chip and a two-group service: each gets
    the plans of its own groups, in any order of calls, and the plans of
    the two-group service are those of a planner no one-chip service
    touched."""
    alone = _planner(_calibration())
    want = [(p.algorithm, p.scheme) for p in
            (alone.choose(b, n, max_out=n, record=False)
             for b, n in SIZES)]
    pl = _planner(_calibration())
    for scope in (SHARED, DISTINCT, SHARED):
        got = [(p.algorithm, p.scheme) for p in
               (pl.choose(b, n, max_out=n, record=False, scope=scope)
                for b, n in SIZES)]
        if scope.shared_groups:
            assert got[:3] == [("shj", "GPU_ONLY")] * 3
        else:
            assert got == want
    assert any(s not in ("GPU_ONLY",) for _, s in want)


def test_paper_join_keeps_phj_dd_on_shared_groups():
    parent = _planner(_calibration())          # two distinct groups
    want = parent.choose(1 << 24, 1 << 24, max_out=(1 << 24) + (1 << 22))
    assert (want.algorithm, want.scheme) == ("phj", "DD")
    pl = _planner(_calibration())
    got = pl.choose(1 << 24, 1 << 24, max_out=(1 << 24) + (1 << 22),
                    c_load=3.0, g_load=0.0, scope=SHARED)
    assert (got.algorithm, got.scheme, got.schedule) == (
        "phj", "DD", want.schedule)
    assert (got.partition_ratio, got.join_ratio) == (
        want.partition_ratio, want.join_ratio)


# ---------------------------------------------------------------------------
# (d) The group-by's host crossings, reckoned from the shapes.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("path", ["one_group", "partitioned"])
def test_groupby_crossings_match_shape_reckoning(path):
    metrics = MetricsRegistry()
    cp = CoProcessor()
    cp.ledger, cp.metrics = TransferLedger(metrics), metrics
    n = 3000
    rng = np.random.default_rng(7)
    keys = rng.integers(0, 500, n).astype(np.int32)
    vals = rng.integers(0, 1000, n).astype(np.int32)
    rel = Relation(jnp.arange(n, dtype=jnp.int32), jnp.asarray(keys))

    def groups_bytes(m):                # ukeys, count, 5 sum chunks, min,
        return 4 * 9 * m + 4            # max, and the group count
    if path == "one_group":
        groupby_coprocessed(cp, rel, vals, agg_ratio=0.0)
        want = {"groupby.values": {"h2d": 4 * n},
                "groupby.collect": {"d2h": groups_bytes(n)}}
        syncs = {"groupby.collect": 1}
    else:
        sched = (3,)
        groupby_coprocessed(cp, rel, vals, schedule=sched,
                            partition_ratio=0.5, agg_ratio=0.5)
        num_parts = 1 << sum(sched)
        own = cp._cut(num_parts, 0.5)
        # Partitioning keeps each key, so pids follow the input keys.
        pid = np.asarray(radix_of(jnp.asarray(keys), shift=0,
                                  bits=sum(sched)))
        ms = [_round_up(max(int(c), 1), cp.lcm)
              for c in ((pid < own).sum(), (pid >= own).sum())]
        want = {"groupby.values": {"h2d": 4 * n},
                "groupby.partition": {"d2h": 8 * n, "h2d": 8 * n},
                "groupby.exchange.pid": {"d2h": 4 * n},
                "groupby.exchange.rows": {"d2h": 8 * n},
                "groupby.exchange.share": {"h2d": 8 * sum(ms)},
                "groupby.collect": {"d2h": sum(groups_bytes(m)
                                               for m in ms)}}
        syncs = {"groupby.partition": 1, "groupby.exchange.pid": 1,
                 "groupby.exchange.rows": 1, "groupby.collect": 1}
    got: dict = {}
    for e in cp.ledger.entries():
        d = got.setdefault(e["stage"], {})
        d[e["direction"]] = d.get(e["direction"], 0) + e["nbytes"]
    assert got == want
    assert {dict(k)["site"]: v for k, v in
            metrics.counter_series("host_syncs").items()} == syncs
    assert metrics.counter_value("host_bytes_moved") == 0


# ---------------------------------------------------------------------------
# (e) plan_changes counts a re-price that changes the plan.
# ---------------------------------------------------------------------------

def test_plan_changes_counts_a_changed_reprice():
    metrics, other = MetricsRegistry(), MetricsRegistry()
    scope = PlanScope(shared_groups=False, metrics=metrics)
    pl = _planner(_calibration())
    first = pl.choose(4096, 1 << 20, max_out=1 << 21, scope=scope)
    # A re-price whose incumbent still wins: no change.
    assert pl.flag_replan() == 1
    again = pl.choose(4096, 1 << 20, max_out=1 << 21, scope=scope)
    assert (again.algorithm, again.scheme) == (first.algorithm,
                                               first.scheme)
    assert metrics.counter_value("plan_changes") == 0
    # The incumbent's phase measured 100x its estimate: a calibration
    # tick, and the re-price moves the plan.
    tag = pl._online_tag(first, "probe")
    v0 = pl.online.version
    pl.online.observe(tag, 1.0, 100.0)
    assert pl.online.version > v0
    moved = pl.choose(4096, 1 << 20, max_out=1 << 21, scope=scope)
    assert (moved.algorithm, moved.scheme) != (first.algorithm, first.scheme)
    assert metrics.counter_series("plan_changes") == {
        (("kind", "join"),): 1}
    # Another service on the same planner counts its own changes only.
    pl.online.observe(pl._online_tag(moved, "probe"), 1.0, 100.0)
    pl.choose(4096, 1 << 20, max_out=1 << 21,
              scope=PlanScope(shared_groups=False, metrics=other))
    assert metrics.counter_value("plan_changes") == 1
