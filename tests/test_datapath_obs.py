"""Data-path observability: cardinality audit, transfer ledger, adaptive
mid-pipeline re-optimization, structural fingerprints, tenant cache budgets.

The skewed-star workload here is the PR's end-to-end story: a fact table
whose first FK column is half junk makes the System-R estimate for the
first join wrong by ~16x; the adaptive executor observes the exact device
cardinality, re-prices the remaining tail, and flips the stage order —
while reproducing the NumPy reference rows exactly (the same
permutation-invariance contract every static plan already honors).
"""
import numpy as np
import pytest

from repro.engine import BuildTableCache, JoinQueryService, QueryPlanner
from repro.obs import (CAUSES, CardinalityAudit, INTERMEDIATE_CAUSES,
                       MetricsRegistry, TransferLedger, q_error)
from repro.queries import (Join, JoinOrderOptimizer, PipelineExecutor,
                           Query, Table, make_star_query, reference_execute)


def make_service(**kw):
    return JoinQueryService(planner=QueryPlanner(delta=0.25),
                            num_workers=kw.pop("num_workers", 2), **kw)


def skewed_star_query(seed: int = 7) -> Query:
    """Seed-deterministic 3-join star built to fool the estimator.

    ``fact.fk0`` is ~50% matching / ~50% junk keys drawn from a wide
    range: the uniform-ndv estimate prices the first join at ~250 rows
    where ~4096 actually survive.  ``d2`` has 40 distinct ids over 400
    rows against a [0, 4000) FK — a x0.1 *shrink* at the true
    intermediate size that the estimate (capped by the ~250-row
    component's ndv) prices as x1.6 *growth*, so the static plan
    schedules it last while the observed cardinality says run it first.
    """
    rng = np.random.default_rng(seed)
    n = 8192
    fk0 = np.where(rng.random(n) < 0.5,
                   rng.integers(0, 128, n),
                   rng.integers(100_000, 200_000, n)).astype(np.int32)
    fact = Table("fact", {
        "fk0": fk0,
        "fk1": rng.integers(0, 144, n).astype(np.int32),
        "fk2": rng.integers(0, 4000, n).astype(np.int32),
        "v": rng.integers(0, 100, n).astype(np.int32)})
    d0 = Table("d0", {"id": np.arange(128, dtype=np.int32),
                      "a": rng.integers(0, 10, 128).astype(np.int32)})
    d1 = Table("d1", {"id": np.arange(144, dtype=np.int32),
                      "b": rng.integers(0, 10, 144).astype(np.int32)})
    d2 = Table("d2", {"id": np.repeat(np.arange(40, dtype=np.int32), 10),
                      "c": rng.integers(0, 10, 400).astype(np.int32)})
    return Query(tables={"fact": fact, "d0": d0, "d1": d1, "d2": d2},
                 joins=(Join("fact", "fk0", "d0", "id"),
                        Join("fact", "fk1", "d1", "id"),
                        Join("fact", "fk2", "d2", "id")),
                 aggregate=("count",))


# ---------------------------------------------------------------------------
# Units: q-error, cardinality audit, transfer ledger.
# ---------------------------------------------------------------------------

def test_q_error_symmetric_and_clamped():
    assert q_error(100, 100) == 1.0
    assert q_error(100, 400) == pytest.approx(4.0)
    assert q_error(400, 100) == pytest.approx(4.0)
    assert q_error(0.3, 0) == 1.0          # both clamp to >= 1: perfect
    assert q_error(0, 8) == pytest.approx(8.0)


def test_cardinality_audit_summary():
    audit = CardinalityAudit(max_records=4)
    for est, obs in ((100, 100), (100, 200), (50, 400)):
        audit.record(stage_type="inner", est_rows=est, observed_rows=obs,
                     depth=1, tenant="t0")
    audit.record(stage_type="semi", est_rows=10, observed_rows=10, depth=2)
    s = audit.summary()
    assert s["count"] == 4
    assert set(s["stage_types"]) == {"inner", "semi"}
    inner = s["stage_types"]["inner"]
    assert inner["count"] == 3 and inner["max"] == pytest.approx(8.0)
    assert np.isfinite(inner["p50"]) and np.isfinite(inner["p95"])
    assert set(s["depths"]) == {"1", "2"}
    assert s["tenants"]["t0"]["count"] == 3
    # Bounded ring: a 5th record drops the oldest.
    audit.record(stage_type="anti", est_rows=1, observed_rows=1)
    assert audit.summary()["count"] == 4


def test_ledger_records_and_sums():
    metrics = MetricsRegistry()
    led = TransferLedger(metrics)
    led.record(100, cause="handoff", stage="stage0", direction="d2h")
    led.record(50, cause="handoff", stage="stage0", direction="d2h")
    led.record(30, cause="fingerprint", stage="adhoc", column="build.key")
    led.record(70, cause="multicol_pack", stage="groupby-sink",
               direction="h2d")
    led.record(999, cause="result", stage="result", column="*")
    led.record(0, cause="handoff")          # no-ops, not recorded
    led.record(-5, cause="handoff")
    by_cause = led.by_cause()
    assert by_cause == {"fingerprint": 30, "multicol_pack": 70,
                        "handoff": 150, "result": 999, "exchange": 0,
                        "base_upload": 0}
    # The flat counter is a sum view over the intermediate causes only.
    assert led.total() == 250
    assert led.total(intermediate_only=False) == 1249
    snap = metrics.snapshot()
    assert snap["host_bytes_moved"] == 250
    assert snap["host_transfer_bytes{cause=handoff,direction=d2h}"] == 150
    assert snap["host_transfer_bytes{cause=result,direction=d2h}"] == 999
    s = led.summary()
    assert s["intermediate_bytes"] == 250 and s["total_bytes"] == 1249
    assert s["crossings"] == 5
    assert s["by_stage"]["stage0"]["handoff"] == 150
    assert s["by_direction"]["h2d"] == 70
    with pytest.raises(ValueError):
        led.record(1, cause="mystery")
    with pytest.raises(ValueError):
        led.record(1, cause="handoff", direction="sideways")


# ---------------------------------------------------------------------------
# Ledger exactness over the pipeline paths.
# ---------------------------------------------------------------------------

def test_ledger_fused_path_attributed_and_quiet():
    """Fused path: zero intermediate bytes, all causes known, handoff == 0,
    and the ledger sum equals the flat counter exactly."""
    query = make_star_query(4096, [256, 128], seed=3, aggregate=None)
    svc = make_service()
    with PipelineExecutor(service=svc) as ex:
        res = ex.run(query)
        assert res.host_bytes_moved == 0
        summ = svc.ledger.summary()
        assert set(summ["by_cause"]) == set(CAUSES)
        assert summ["by_cause"]["handoff"] == 0
        assert summ["intermediate_bytes"] == \
            svc.stats()["host_bytes_moved"] == 0
        # Result delivery is attributed under ``result`` without ever
        # touching the intermediate counter.
        rows = res.rows_array()
        assert rows.shape[0] == res.rows
        assert svc.ledger.by_cause()["result"] > 0
        assert svc.stats()["host_bytes_moved"] == 0


def test_ledger_host_path_sum_matches_counter():
    """Host-materialize path: every byte the pipeline reports is in the
    ledger — sum over intermediate causes == host_bytes_moved, exactly."""
    query = make_star_query(4096, [256, 128], seed=3, aggregate=("count",))
    svc = make_service()
    opt = JoinOrderOptimizer(svc.planner, handoff="host")
    with PipelineExecutor(service=svc, optimizer=opt,
                          handoff="host") as ex:
        res = ex.run(query)
        assert res.host_bytes_moved > 0
        st = svc.stats()
        summ = st["host_transfer_ledger"]
        assert summ["intermediate_bytes"] == st["host_bytes_moved"] \
            == res.host_bytes_moved
        assert summ["by_cause"]["handoff"] == res.host_bytes_moved
        assert sum(summ["by_cause"][c] for c in INTERMEDIATE_CAUSES) \
            == st["host_bytes_moved"]


def test_ledger_multicol_groupby_cause_split():
    """Multi-column group-by on the fused path: the host pack shows up as
    ``multicol_pack`` (attributed!), never as ``handoff``."""
    query = make_star_query(4096, [128, 64], seed=5, aggregate=("count",),
                            group_by=("D0.a", "D1.a"))
    svc = make_service()
    with PipelineExecutor(service=svc) as ex:
        res = ex.run(query)
        by_cause = svc.ledger.by_cause()
        assert by_cause["handoff"] == 0
        assert by_cause["multicol_pack"] > 0
        assert by_cause["multicol_pack"] == res.host_bytes_moved
        assert svc.stats()["host_bytes_moved"] == res.host_bytes_moved


def test_cardinality_recorded_for_every_stage():
    query = make_star_query(4096, [256, 128], seed=3)
    svc = make_service()
    with PipelineExecutor(service=svc) as ex:
        ex.run(query)
        st = svc.stats()["cardinality_error"]
        assert st["count"] == 2                      # one per join stage
        assert "inner" in st["stage_types"]
        t = st["stage_types"]["inner"]
        assert t["count"] == 2
        assert np.isfinite(t["p50"]) and np.isfinite(t["p95"])
        assert all(r["observed_rows"] >= 0
                   for r in svc.cardinality.records())


# ---------------------------------------------------------------------------
# Structural fingerprints: the fused path stops pulling key columns.
# ---------------------------------------------------------------------------

def test_fused_fingerprints_no_pull_and_cache_hits():
    """Repeating a fused pipeline hits the build cache via structural
    fingerprints — zero ``fingerprint``-cause bytes on either run."""
    query = make_star_query(4096, [256, 128], seed=11)
    svc = make_service()
    with PipelineExecutor(service=svc) as ex:
        first = ex.run(query)
        hits_before = svc.cache.stats()["hits"]
        again = ex.run(query)
        assert again.aggregate == first.aggregate
        assert svc.cache.stats()["hits"] > hits_before
        assert svc.ledger.by_cause()["fingerprint"] == 0
        assert svc.stats()["host_bytes_moved"] == 0


def test_host_path_fingerprints_hash_before_upload():
    """The host path fingerprints from the host copy pre-upload: no
    fingerprint pulls there either, and repeats still hit the cache."""
    query = make_star_query(4096, [256], seed=11)
    svc = make_service()
    opt = JoinOrderOptimizer(svc.planner, handoff="host")
    with PipelineExecutor(service=svc, optimizer=opt,
                          handoff="host") as ex:
        ex.run(query)
        hits_before = svc.cache.stats()["hits"]
        ex.run(query)
        assert svc.cache.stats()["hits"] > hits_before
        assert svc.ledger.by_cause()["fingerprint"] == 0


# ---------------------------------------------------------------------------
# Adaptive mid-pipeline re-optimization.
# ---------------------------------------------------------------------------

def test_adaptive_replan_flips_stage_order():
    query = skewed_star_query()
    ref_rows, ref_agg = reference_execute(query)

    svc_static = make_service()
    with PipelineExecutor(service=svc_static) as ex:
        static_res = ex.run(query)
    static_order = [str(s.join) for s in static_res.physical.stages]

    svc = make_service()
    with PipelineExecutor(service=svc, adaptive=True) as ex:
        res = ex.run(query)
        adaptive_order = [str(s.join) for s in res.physical.stages]
        # The replan happened, flipped the executed order, and left a
        # structured record + counter behind.
        assert len(res.replans) >= 1
        assert adaptive_order != static_order
        rec = res.replans[0]
        assert rec["worst_q_error"] >= 2.0
        assert rec["old_tail"] != rec["new_tail"]
        assert rec["after_stages"] >= 1
        assert svc.metrics.snapshot()["pipeline_replans"] >= 1
        assert svc.metrics.events("replan")
        # Row-exactness survives the mid-flight re-order, fused-quiet.
        assert res.aggregate == static_res.aggregate == ref_agg
        assert np.array_equal(res.rows_array(), ref_rows)
        assert res.host_bytes_moved == 0
        # to_dict carries the replans for bench payloads.
        assert res.to_dict()["replans"] == res.replans


def test_adaptive_noop_on_accurate_estimates():
    """Uniform star: estimates are good, so no replan fires and results
    match the static run exactly."""
    query = make_star_query(4096, [256, 128, 64], seed=3)
    ref_rows, ref_agg = reference_execute(query)
    svc = make_service()
    with PipelineExecutor(service=svc, adaptive=True) as ex:
        res = ex.run(query)
        assert res.replans == []
        assert res.aggregate == ref_agg
        assert np.array_equal(res.rows_array(), ref_rows)
        assert svc.metrics.snapshot().get("pipeline_replans", 0) == 0


def test_adaptive_group_by_and_variants_still_exact():
    query = make_star_query(4096, [256, 128], seed=9, aggregate=("count",),
                            group_by=("D0.a",), join_kinds=("inner", "semi"))
    ref_rows, _ = reference_execute(query)
    with PipelineExecutor(service=make_service(), adaptive=True) as ex:
        res = ex.run(query)
        assert np.array_equal(res.rows_array(), ref_rows)


def test_reprice_remaining_guards():
    opt = JoinOrderOptimizer(QueryPlanner(delta=0.25))
    query = skewed_star_query()
    j0, j1, j2 = query.joins
    observed = {id(j0): 4096}
    # A single-edge tail cannot be re-ordered.
    assert opt.reprice_remaining(query, [j0, j1], [j2], observed) is None
    # Outer queries pin textual order: never re-ordered.
    rng = np.random.default_rng(0)
    t0 = Table("t0", {"id": np.arange(256, dtype=np.int32),
                      "fka": rng.integers(0, 64, 256).astype(np.int32),
                      "fkb": rng.integers(0, 64, 256).astype(np.int32)})
    ta = Table("ta", {"id": np.arange(64, dtype=np.int32)})
    tb = Table("tb", {"id": np.arange(64, dtype=np.int32)})
    outer = Query(tables={"t0": t0, "ta": ta, "tb": tb},
                  joins=(Join("t0", "fka", "ta", "id", kind="left_outer"),
                         Join("t0", "fkb", "tb", "id"),
                         Join("t0", "id", "t0", "id")))
    o0 = outer.joins[0]
    assert opt.reprice_remaining(
        outer, [o0], list(outer.joins[1:]), {id(o0): 256}) is None


def test_replan_margin_hysteresis():
    pl = QueryPlanner(delta=0.25, replan_margin=0.8)
    assert pl.replan_beats(0.7, 1.0)
    assert not pl.replan_beats(0.9, 1.0)     # near-tie: incumbent stays
    assert not pl.replan_beats(0.8, 1.0)     # margin is strict


# ---------------------------------------------------------------------------
# Per-tenant cache byte budgets.
# ---------------------------------------------------------------------------

def _filler(nbytes: int) -> np.ndarray:
    return np.zeros(nbytes // 4, dtype=np.int32)


def test_tenant_budget_evicts_own_entries_first():
    reg = MetricsRegistry()
    cache = BuildTableCache(budget_bytes=1 << 20,
                            tenant_budget_bytes=1024)
    cache.register_metrics(reg)
    assert cache.put("hot:a", _filler(512), tenant="hot")
    assert cache.put("cold:a", _filler(512), tenant="cold")
    assert cache.put("hot:b", _filler(512), tenant="hot")
    # Third hot entry pushes the tenant over its cap: its own LRU entry
    # goes, the cold tenant's survives.
    assert cache.put("hot:c", _filler(512), tenant="hot")
    assert cache.peek("hot:a") is None
    assert cache.peek("cold:a") is not None
    assert cache.peek("hot:b") is not None
    st = cache.stats()
    assert st["budget_evictions"] == 1 and st["evictions"] == 1
    assert st["tenant_bytes"]["hot"] == 1024
    snap = reg.snapshot()
    assert snap["cache_budget_evictions{kind=table,tenant=hot}"] == 1
    assert snap["cache_evictions{kind=table,tenant=hot}"] == 1
    ev = reg.events("cache_eviction")
    assert ev and ev[-1]["reason"] == "tenant_budget"
    assert ev[-1]["victim"] == "hot"


def test_tenant_budget_rejects_oversized_entry():
    cache = BuildTableCache(budget_bytes=1 << 20,
                            tenant_budget_bytes={"small": 256})
    assert not cache.put("small:big", _filler(512), tenant="small")
    assert len(cache) == 0
    # Unlisted tenants are uncapped under a dict budget.
    assert cache.put("other:big", _filler(512), tenant="other")


def test_shared_capacity_sweep_unchanged():
    reg = MetricsRegistry()
    cache = BuildTableCache(budget_bytes=1024)
    cache.register_metrics(reg)
    assert cache.put("a", _filler(512), tenant="t0")
    assert cache.put("b", _filler(512), tenant="t1")
    assert cache.put("c", _filler(512), tenant="t2")   # evicts "a"
    assert cache.peek("a") is None
    st = cache.stats()
    assert st["evictions"] == 1 and st["budget_evictions"] == 0
    ev = reg.events("cache_eviction")
    assert ev[-1]["reason"] == "capacity"


def test_service_accepts_tenant_cache_budget():
    svc = make_service(tenant_cache_budget_bytes=64 << 10)
    try:
        assert svc.cache.tenant_budget_bytes == 64 << 10
    finally:
        svc.close()


# ---------------------------------------------------------------------------
# Host crossings: CoProcessor's exchange, the executor's base uploads.
# ---------------------------------------------------------------------------

def _round_up(n, k):
    return -(-n // k) * k


def test_phj_exchange_bytes_match_shape_reckoning():
    """Every crossing of a CPU PHJ, reckoned from its shapes: partition
    pieces down and back up, the ownership split (a bool pid mask per
    side per group and both int32 columns per side once down, each
    group's share up at its capacity),
    and both groups' results down and back up for the concat."""
    from repro.core import CoProcessor, uniform_relation
    from repro.core.coprocess import _share_capacity
    from repro.core.relation import radix_of
    metrics = MetricsRegistry()
    cp = CoProcessor()
    cp.ledger, cp.metrics = TransferLedger(metrics), metrics
    n_r, n_s, max_out, sched = 3072, 2048, 9000, (3, 2)
    b = uniform_relation(n_r, seed=41)
    s = uniform_relation(n_s, key_range=n_r, seed=42)
    cp.phj(b, s, schedule=sched, shj_bits=2, max_out=max_out,
           partition_ratio=0.5, join_ratio=0.5)
    bits = sum(sched)
    num_parts = 1 << bits
    own = cp._cut(num_parts, 0.5)
    groups = 2                     # both own partitions at join_ratio 0.5
    share_up = 0
    for rel in (b, s):
        pid = np.asarray(radix_of(rel.key, shift=0, bits=bits))
        for owned, mask in ((own, pid < own), (num_parts - own, pid >= own)):
            cap, _ = _share_capacity(rel.size, owned, num_parts,
                                     int(mask.sum()), cp.lcm)
            share_up += 8 * cap
    mo = _round_up(max_out, 8) + 64
    result = 2 * 4 * mo + 4        # probe_rid + build_rid + count
    want = {
        "phj.partition": {"d2h": 8 * (n_r + n_s), "h2d": 8 * (n_r + n_s)},
        "phj.exchange.mask": {"d2h": groups * (n_r + n_s)},
        "phj.exchange.rows": {"d2h": 8 * (n_r + n_s)},
        "phj.exchange.share": {"h2d": share_up},
        "phj.join.collect": {"d2h": groups * result, "h2d": groups * result},
    }
    got: dict = {}
    for e in cp.ledger.entries():
        assert e["cause"] == "exchange"
        d = got.setdefault(e["stage"], {})
        d[e["direction"]] = d.get(e["direction"], 0) + e["nbytes"]
    assert got == want
    assert cp.ledger.by_cause()["exchange"] == sum(
        v for d in want.values() for v in d.values())
    # Not intermediate traffic: the flat counter never moved.
    assert metrics.counter_value("host_bytes_moved") == 0


def _two_table_query(n_fact=4096, n_dim=256, seed=5):
    rng = np.random.default_rng(seed)
    fact = Table("F", {"fk": rng.integers(0, n_dim, n_fact),
                       "v": rng.integers(0, 100, n_fact)})
    dim = Table("D", {"id": np.arange(n_dim), "a": rng.integers(0, 9, n_dim)})
    return Query(tables={"F": fact, "D": dim},
                 joins=(Join("F", "fk", "D", "id"),), aggregate=("count",))


@pytest.mark.parametrize("handoff", ["device", "host"])
def test_base_upload_equals_scanned_column_bytes(handoff):
    """Unfiltered scans: the fused path uploads each join key column once
    (4 bytes a row, no scan index); the host path uploads rid + key per
    side (8 bytes a row).  None of it is intermediate traffic."""
    n_fact, n_dim = 4096, 256
    query = _two_table_query(n_fact, n_dim)
    svc = make_service()
    opt = JoinOrderOptimizer(svc.planner, handoff=handoff)
    with PipelineExecutor(service=svc, optimizer=opt,
                          handoff=handoff) as ex:
        res = ex.run(query)
        assert res.aggregate == reference_execute(query)[1]
        per_row = 4 if handoff == "device" else 8
        assert svc.ledger.by_cause()["base_upload"] == \
            per_row * (n_fact + n_dim)
        assert svc.stats()["host_bytes_moved"] == res.host_bytes_moved


@pytest.mark.parametrize("case", ["fused_star", "multicol_groupby"])
def test_new_causes_leave_host_bytes_moved_unchanged(case):
    """``exchange`` and ``base_upload`` are ledgered but not intermediate:
    the fused path's ``host_bytes_moved`` reads what it always read."""
    if case == "fused_star":
        query = make_star_query(4096, [256, 128], seed=3, aggregate=None)
    else:
        query = make_star_query(4096, [128, 64], seed=5,
                                aggregate=("count",),
                                group_by=("D0.a", "D1.a"))
    svc = make_service()
    with PipelineExecutor(service=svc) as ex:
        res = ex.run(query)
        by_cause = svc.ledger.by_cause()
        assert by_cause["base_upload"] > 0
        assert svc.stats()["host_bytes_moved"] == res.host_bytes_moved == \
            sum(by_cause[c] for c in INTERMEDIATE_CAUSES)
        if case == "fused_star":
            assert res.host_bytes_moved == 0
        else:
            assert res.host_bytes_moved == by_cause["multicol_pack"] > 0
