"""Co-processed relational operators: group-by aggregation + join variants.

Kernel-vs-ref parity (interpret + compiled jnp path), operator-vs-NumPy-
oracle checks across the edge cases (empty groups, all-unmatched probes,
duplicate keys), planner pricing of the new operators, and declarative
``group_by`` / join-``kind`` queries verified row/value-exact against
``reference_execute`` — hypothesis-driven where available, a deterministic
sweep otherwise (test_queries.py conventions).
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (CoProcessor, join_oracle, uniform_relation,
                        unique_relation)
from repro.core.hash_table import build_hash_table, default_num_buckets
from repro.core.relation import Relation, probe_with_selectivity
from repro.engine import (GroupByQuery, JoinQuery, JoinQueryService,
                          QueryPlanner)
from repro.ops import (groupby_ref, join_variant_oracle,
                       probe_hash_table_variant, probe_table_variant)
from repro.ops.groupby import grouped_agg
from repro.queries import (Filter, Join, JoinOrderOptimizer,
                           PipelineExecutor, Query, Table, make_star_query,
                           reference_execute)


@pytest.fixture(scope="module")
def cp():
    return CoProcessor()


@pytest.fixture(scope="module")
def planner():
    return QueryPlanner(delta=0.25)


def run_pipeline(query, physical=None, optimizer=None, num_workers=2):
    svc = JoinQueryService(planner=QueryPlanner(delta=0.25),
                           num_workers=num_workers)
    with PipelineExecutor(service=svc, optimizer=optimizer) as ex:
        return ex.run(query, physical), svc


# ---------------------------------------------------------------------------
# Segmented-aggregation kernel: interpret-mode Pallas vs jnp oracle.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,slots", [(1024, 16), (2048, 128), (8192, 1024),
                                     (2048, 300), (1024, 5)])
def test_seg_agg_kernel(n, slots, rng):
    from repro.kernels.agg.agg import seg_agg_pallas
    from repro.kernels.agg.ref import seg_agg_ref
    gid = jnp.asarray(rng.integers(-1, slots, n).astype(np.int32))
    val = jnp.asarray(rng.integers(-1000, 1000, n).astype(np.int32))
    got = seg_agg_pallas(gid, val, num_slots=slots, interpret=True)
    exp = seg_agg_ref(gid, val, num_slots=slots)
    for g, e, name in zip(got, exp, ("count", "sum", "min", "max")):
        assert (np.asarray(g) == np.asarray(e)).all(), name


@pytest.mark.parametrize("wrap32", [False, True])
@pytest.mark.parametrize("n", [1000, 3000])
def test_segmented_aggregate_pads_to_tiles(n, wrap32, rng):
    """The dispatcher pads sizes off the 1024 tile with gid -1 tuples;
    the kernel's answer is the jnp path's, channel layout included."""
    from repro.kernels.agg.ops import segmented_aggregate
    gid = jnp.asarray(rng.integers(-1, 64, n).astype(np.int32))
    val = jnp.asarray(rng.integers(-2**31, 2**31 - 1, n).astype(np.int32))
    got = segmented_aggregate(gid, val, num_slots=64, interpret=True,
                              wrap32=wrap32)
    exp = segmented_aggregate(gid, val, num_slots=64, use_pallas=False,
                              wrap32=wrap32)
    for g, e, name in zip(got, exp, ("count", "sum", "min", "max")):
        assert (np.asarray(g) == np.asarray(e)).all(), name


def test_seg_agg_kernel_empty_slots(rng):
    """Slots no tuple maps to report the neutral elements."""
    from repro.kernels.agg.agg import INT32_MAX, INT32_MIN, seg_agg_pallas
    gid = jnp.asarray(np.zeros(1024, np.int32))          # everything slot 0
    val = jnp.asarray(rng.integers(0, 9, 1024).astype(np.int32))
    cnt, sm, mn, mx = seg_agg_pallas(gid, val, num_slots=8, interpret=True,
                                     wrap32=True)
    assert int(cnt[0]) == 1024 and (np.asarray(cnt[1:]) == 0).all()
    assert (np.asarray(mn[1:]) == INT32_MAX).all()
    assert (np.asarray(mx[1:]) == INT32_MIN).all()
    assert int(sm[0]) == int(np.asarray(val).sum())


def test_seg_agg_kernel_wide_sums(rng):
    """The default wide path is int64-exact where int32 would wrap."""
    from repro.kernels.agg.agg import seg_agg_pallas, wide_sums_to_int64
    gid = jnp.asarray((np.arange(2048) % 4).astype(np.int32))
    base = rng.integers(-2**31, 2**31, 2048).astype(np.int32)
    val = jnp.asarray(base)
    cnt, sm, mn, mx = seg_agg_pallas(gid, val, num_slots=8, interpret=True)
    assert sm.shape == (5, 8)
    got = wide_sums_to_int64(np.asarray(sm))
    exp = np.zeros(8, np.int64)
    np.add.at(exp, np.arange(2048) % 4, base.astype(np.int64))
    assert (got == exp).all()
    # ... and the wrap32 channel reproduces the old modular accumulator.
    _, sm32, _, _ = seg_agg_pallas(gid, val, num_slots=8, interpret=True,
                                   wrap32=True)
    wrapped = (got & 0xFFFFFFFF).astype(np.uint32).view(np.int32)
    assert (np.asarray(sm32) == wrapped).all()


def _check_groupby(result, keys, values):
    ref = groupby_ref(keys, values)
    s = result.sorted()
    assert s.num_groups == ref.num_groups
    for a, b in ((s.keys, ref.keys), (s.counts, ref.counts),
                 (s.sums, ref.sums), (s.mins, ref.mins), (s.maxs, ref.maxs)):
        assert (a == b).all()


@pytest.mark.parametrize("n,krange", [(1024, 8), (4096, 256), (4096, 4096)])
def test_grouped_agg_matches_oracle(n, krange, rng):
    keys = rng.integers(0, krange, n).astype(np.int32)
    vals = rng.integers(-100, 100, n).astype(np.int32)
    rel = Relation(jnp.arange(n, dtype=jnp.int32), jnp.asarray(keys))
    uk, cnt, sm, mn, mx, ng = grouped_agg(rel, jnp.asarray(vals),
                                          num_slots=n)
    from repro.kernels.agg import wide_sums_to_int64
    ref = groupby_ref(keys, vals)
    ng = int(ng)
    assert ng == ref.num_groups
    o = np.argsort(np.asarray(uk[:ng]))
    assert (np.asarray(uk[:ng])[o] == ref.keys).all()
    assert (np.asarray(cnt[:ng])[o] == ref.counts).all()
    assert (wide_sums_to_int64(np.asarray(sm))[:ng][o] == ref.sums).all()
    assert (np.asarray(mn[:ng])[o] == ref.mins).all()
    assert (np.asarray(mx[:ng])[o] == ref.maxs).all()


# ---------------------------------------------------------------------------
# Co-processed group-by operator.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("schedule,pr,ar", [((3, 2), 0.5, 0.5),
                                            ((4,), 1.0, 0.25),
                                            (None, 1.0, 1.0),
                                            (None, 0.0, 0.0)])
def test_coprocessed_groupby(cp, schedule, pr, ar, rng):
    n = 4096
    keys = rng.integers(0, 64, n).astype(np.int32)
    vals = rng.integers(0, 100, n).astype(np.int32)
    rel = Relation(jnp.arange(n, dtype=jnp.int32), jnp.asarray(keys))
    res, timing = cp.groupby(rel, vals, schedule=schedule,
                             partition_ratio=pr, agg_ratio=ar)
    _check_groupby(res, keys, vals)
    assert "agg" in timing.phase_s
    if schedule:
        assert timing.phase_s["partition"] > 0


def test_groupby_edge_cases(cp):
    # Empty input -> zero groups.
    empty = Relation(jnp.zeros((0,), jnp.int32), jnp.zeros((0,), jnp.int32))
    res, _ = cp.groupby(empty, np.zeros(0, np.int32))
    assert res.num_groups == 0 and res.sorted().keys.shape == (0,)
    # One duplicate key -> one group carrying everything.
    n = 1024
    keys = np.full(n, 7, np.int32)
    vals = np.arange(n, dtype=np.int32)
    rel = Relation(jnp.arange(n, dtype=jnp.int32), jnp.asarray(keys))
    res, _ = cp.groupby(rel, vals, schedule=(2,), partition_ratio=0.5,
                        agg_ratio=0.5)
    assert res.num_groups == 1 and int(res.counts[0]) == n
    assert int(res.mins[0]) == 0 and int(res.maxs[0]) == n - 1
    _check_groupby(res, keys, vals)


def test_groupby_sum_width_modes(cp):
    # Values that overflow int32 by a wide margin: the default wide path
    # must be int64-exact, and wrap32=True must reproduce the legacy
    # modular accumulator exactly (oracle parity in both modes).
    n = 1024
    keys = np.zeros(n, np.int32)
    vals = np.full(n, 2**30, np.int32)       # overflows far past int32
    rel = Relation(jnp.arange(n, dtype=jnp.int32), jnp.asarray(keys))
    res, _ = cp.groupby(rel, vals)
    assert res.sums.dtype == np.int64
    assert int(res.sums[0]) == n * 2**30     # no silent wrap
    _check_groupby(res, keys, vals)
    res32, _ = cp.groupby(rel, vals, wrap32=True)
    assert res32.sums.dtype == np.int32
    ref32 = groupby_ref(keys, vals, wrap32=True)
    assert (res32.sorted().sums == ref32.sums).all()
    # The separate-partials DD merge keeps wide sums exact too.
    res_dd, _ = cp.groupby(rel, vals, agg_ratio=0.5)
    assert int(res_dd.sorted().sums[0]) == n * 2**30


# ---------------------------------------------------------------------------
# Join variants: kernel + co-processed probe vs oracle.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["semi", "anti", "left_outer"])
@pytest.mark.parametrize("sel", [0.0, 0.5, 1.0])
def test_probe_variant_matches_oracle(cp, kind, sel):
    b = unique_relation(512, seed=41)
    p = probe_with_selectivity(b, 1024, selectivity=sel, seed=42)
    table = build_hash_table(b, default_num_buckets(512))
    exp = join_variant_oracle(b, p, kind)
    got = probe_hash_table_variant(p, table, 4096, kind).valid_pairs()
    assert got.shape == exp.shape and (got == exp).all()
    res, _ = probe_table_variant(cp, p, table, kind=kind, max_out=4096,
                                 ratios=(0.5,) * 4)
    gotc = res.valid_pairs()
    assert gotc.shape == exp.shape and (gotc == exp).all()


def test_probe_variant_duplicate_keys(cp):
    # Duplicate build keys: semi must not multiply rows, outer must.
    b = uniform_relation(512, key_range=64, seed=5)      # heavy duplicates
    p = uniform_relation(512, key_range=128, seed=6)
    table = build_hash_table(b, default_num_buckets(512))
    for kind in ("semi", "anti", "left_outer"):
        exp = join_variant_oracle(b, p, kind)
        got = probe_hash_table_variant(p, table, 16384, kind).valid_pairs()
        assert got.shape == exp.shape and (got == exp).all(), kind
    n_semi = join_variant_oracle(b, p, "semi").shape[0]
    n_anti = join_variant_oracle(b, p, "anti").shape[0]
    assert n_semi + n_anti == 512
    assert join_variant_oracle(b, p, "left_outer").shape[0] >= 512


# ---------------------------------------------------------------------------
# Planner: variant + group-by pricing.
# ---------------------------------------------------------------------------

def test_planner_semi_probe_cheaper_than_inner(planner):
    inner = planner.choose(65536, 65536, max_out=65536)
    semi = planner.choose(65536, 65536, max_out=65536, kind="semi")
    assert semi.kind == "semi" and semi.algorithm == "shj"
    # No p4 payload gather: the semi probe estimate must be cheaper.
    assert semi.est_probe_s < inner.est_probe_s


def test_planner_variant_never_phj(planner):
    big = planner.choose(1 << 24, 1 << 24, max_out=1024, kind="anti")
    assert big.algorithm == "shj"            # phj has no variant emission


def test_planner_groupby_schemes(planner):
    small = planner.choose_groupby(4096)
    assert small.algorithm == "groupby"
    assert small.scheme in ("CPU_ONLY", "GPU_ONLY", "DD")
    big = planner.choose_groupby(1 << 24)
    assert big.scheme == "DD" and big.schedule is not None
    assert big.est_s > 0 and sum(big.schedule) > 0


def test_planner_groupby_feedback():
    from repro.core import Timing
    pl = QueryPlanner(delta=0.25)
    plan = pl.choose_groupby(8192)
    before = plan.est_s
    t = Timing()
    t.phase_s = {"partition": 100.0 * max(plan.est_build_s, 1e-3),
                 "agg": 100.0 * max(plan.est_probe_s, 1e-3)}
    pl.observe(plan, t)
    after = pl.choose_groupby(8192).est_s
    assert after > before                    # scales moved the estimate


# ---------------------------------------------------------------------------
# Service: group-by queries + variant joins through the engine.
# ---------------------------------------------------------------------------

def test_service_groupby_query(cp, rng):
    svc = JoinQueryService(cp=cp, planner=QueryPlanner(delta=0.25),
                           num_workers=0)
    n = 4096
    keys = rng.integers(0, 128, n).astype(np.int32)
    vals = rng.integers(0, 100, n).astype(np.int32)
    rel = Relation(jnp.arange(n, dtype=jnp.int32), jnp.asarray(keys))
    out = svc.execute(GroupByQuery(keys=rel, values=vals, query_id=1))
    assert out.plan.algorithm == "groupby"
    _check_groupby(out.result, keys, vals)
    d = out.to_dict()
    assert d["algorithm"] == "groupby" and d["matches"] == \
        out.result.num_groups


def test_service_variant_join_uses_table_cache(cp):
    svc = JoinQueryService(cp=cp, planner=QueryPlanner(delta=0.25),
                           num_workers=0)
    b = unique_relation(2048, seed=3)
    p = uniform_relation(4096, key_range=4096, seed=4)
    exp = join_variant_oracle(b, p, "semi")
    o1 = svc.execute(JoinQuery(build=b, probe=p, kind="semi",
                               max_out=8192, query_id=1))
    # Inner query against the same build side: the variant's table is
    # reusable (and vice versa) — same fingerprint, same CSR table.
    o2 = svc.execute(JoinQuery(build=b, probe=p, kind="inner",
                               max_out=16384, query_id=2))
    o3 = svc.execute(JoinQuery(build=b, probe=p, kind="semi",
                               max_out=8192, query_id=3))
    assert (o1.result.valid_pairs() == exp).all()
    assert (o3.result.valid_pairs() == exp).all()
    assert (o2.result.valid_pairs() == join_oracle(b, p)).all()
    assert o2.cache_hit and o3.cache_hit
    assert o1.plan.kind == "semi" and o1.to_dict()["kind"] == "semi"


def test_service_probe_partition_reuse(cp):
    # PHJ-forced planner: both sides' partition layouts are cached, so a
    # replayed (build, probe) pair skips every n1–n3 pass.
    pl = QueryPlanner(delta=0.25, cache_bytes=1 << 10, rand_penalty=8.0,
                      phj_overhead_s=0.0)
    assert pl.choose(4096, 4096, max_out=8192).algorithm == "phj"
    svc = JoinQueryService(cp=cp, planner=pl, num_workers=0)
    b = uniform_relation(4096, seed=3)
    s = uniform_relation(4096, key_range=4096, seed=4)
    exp = join_oracle(b, s)
    outs = [svc.execute(JoinQuery(build=b, probe=s, query_id=i,
                                  max_out=4 * 4096 + 1024))
            for i in range(2)]
    assert outs[0].plan.algorithm == "phj"
    assert not outs[0].probe_partition_cache_hit
    assert outs[1].probe_partition_cache_hit
    assert outs[1].partition_cache_hit
    assert outs[1].timing.notes.get("probe_parts_reused")
    for o in outs:
        assert (o.result.valid_pairs() == exp).all()
    st = svc.cache.stats()
    assert st["probe_partition_hits"] == 1
    assert st["probe_partition_misses"] == 1
    assert st["probe_partition_puts"] == 1


# ---------------------------------------------------------------------------
# Declarative layer: group_by + join kinds end-to-end vs the reference.
# ---------------------------------------------------------------------------

def test_query_groupby_validation():
    t = Table("t", {"id": np.arange(8)})
    with pytest.raises(ValueError, match="group_by"):
        Query(tables={"t": t}, joins=(), group_by=("t.nope",))
    with pytest.raises(ValueError, match="unknown aggregate"):
        Query(tables={"t": t}, joins=(), aggregate=("median", "t.id"))
    with pytest.raises(ValueError, match="avg over unknown column"):
        Query(tables={"t": t}, joins=(), aggregate=("avg", "t.nope"))
    # Semi filter tables are consumed: no reuse in other edges/group-by.
    u = Table("u", {"id": np.arange(8), "a": np.arange(8)})
    with pytest.raises(ValueError, match="no other join edge"):
        Query(tables={"t": t, "u": u},
              joins=(Join("t", "id", "u", "id", kind="semi"),
                     Join("t", "id", "u", "a")))
    with pytest.raises(ValueError, match="consumed"):
        Query(tables={"t": t, "u": u},
              joins=(Join("t", "id", "u", "id", kind="semi"),),
              group_by=("u.a",))
    with pytest.raises(ValueError, match="must be inner"):
        Query(tables={"t": t}, joins=(Join("t", "id", "t", "id",
                                           kind="semi"),))


def _run_vs_reference(q, optimizer=None, num_workers=2):
    ref_rows, ref_agg = reference_execute(q)
    res, _ = run_pipeline(q, optimizer=optimizer, num_workers=num_workers)
    assert res.aggregate == ref_agg
    got = res.rows_array()
    assert got.shape == ref_rows.shape and (got == ref_rows).all()
    return res


def test_semi_anti_star_matches_reference():
    q = make_star_query(2048, [256, 128], selectivities=[0.5, 0.4], seed=5,
                        join_kinds=["semi", "anti"], aggregate=("count",))
    res = _run_vs_reference(q)
    assert res.aggregate > 0                 # non-degenerate


def test_left_outer_matches_reference():
    q = make_star_query(1024, [64, 128], selectivities=[0.05, None], seed=7,
                        join_kinds=["left_outer", "inner"])
    res = _run_vs_reference(q)
    assert res.rows >= 1024                  # every fact row preserved


def test_left_outer_empty_build_side():
    # The preserved side survives even when the filter empties the right
    # table: every probe row emits once, all build columns NULL.
    q = make_star_query(256, [64], seed=8, join_kinds=["left_outer"])
    q.tables["D0"] = q.tables["D0"].with_filters(Filter("a", 2000, 2001))
    ref_rows, ref_agg = reference_execute(q)
    res, _ = run_pipeline(q)
    assert res.rows == 256 == ref_agg == res.aggregate
    got = res.rows_array()
    assert got.shape == ref_rows.shape and (got == ref_rows).all()


def test_join_on_null_padded_column_rejected():
    # A later join keyed on an outer join's NULL-padded columns would put
    # NULL_VALUE keys in front of the executor — rejected at construction.
    a = Table("a", {"id": np.arange(8, dtype=np.int32),
                    "b": np.arange(8, dtype=np.int32)})
    b = Table("b", {"id": np.arange(8, dtype=np.int32)})
    f = Table("f", {"k": np.arange(16, dtype=np.int32) % 8})
    with pytest.raises(ValueError, match="nullable"):
        Query(tables={"f": f, "a": a, "b": b},
              joins=(Join("f", "k", "a", "id", kind="left_outer"),
                     Join("a", "b", "b", "id")))
    # ...but an edge BEFORE the outer join sees the table pre-padding.
    Query(tables={"f": f, "a": a, "b": b},
          joins=(Join("a", "b", "b", "id"),
                 Join("f", "k", "a", "id", kind="left_outer")))


def test_left_outer_is_not_reordered(planner):
    opt = JoinOrderOptimizer(planner)
    q = make_star_query(512, [64, 64], seed=9,
                        join_kinds=["left_outer", "inner"])
    assert opt.enumerate_orders(q) == [q.joins]
    assert opt.optimize(q).order == q.joins


def test_groupby_query_through_service():
    q = make_star_query(4096, [256, 128], selectivities=[0.2, None],
                        seed=11, join_kinds=["inner", "semi"],
                        group_by=("F.g",), aggregate=("sum", "F.m"))
    res, svc = run_pipeline(q)
    ref_rows, _ = reference_execute(q)
    got = res.rows_array()
    assert got.shape == ref_rows.shape and (got == ref_rows).all()
    # The sink ran through the service as its own engine query.
    assert len(res.outcomes) == len(q.joins) + 1
    assert res.outcomes[-1].plan.algorithm == "groupby"
    assert svc.stats()["completed"] == len(q.joins) + 1


@pytest.mark.parametrize("agg", [("count",), ("min", "F.m"),
                                 ("avg", "F.m")])
def test_groupby_aggregates_match_reference(agg):
    q = make_star_query(1024, [128], seed=13, group_by=("F.g",),
                        aggregate=agg)
    _run_vs_reference(q, num_workers=0)


def test_multi_column_groupby_matches_reference():
    q = make_star_query(2048, [64], seed=15, group_by=("F.g", "D0.a"),
                        aggregate=("avg", "F.m"))
    _run_vs_reference(q)


def test_empty_groupby_pipeline():
    q = make_star_query(512, [64, 64], seed=17, group_by=("F.g",))
    q.tables["D0"] = q.tables["D0"].with_filters(Filter("a", 2000, 2001))
    ref_rows, _ = reference_execute(q)
    res, _ = run_pipeline(q)
    assert res.rows == 0 and res.rows_array().shape == ref_rows.shape


def test_scan_fusion_skips_filtered_materialization():
    # Satellite: the executor must not materialize filtered base tables
    # on the host before their first join (Table.filtered() untouched).
    q = make_star_query(1024, [256], selectivities=[0.1], seed=19)
    ref_rows, ref_agg = reference_execute(
        make_star_query(1024, [256], selectivities=[0.1], seed=19))
    res, _ = run_pipeline(q)
    assert res.aggregate == ref_agg and (res.rows_array() == ref_rows).all()
    assert q.tables["D0"]._filtered is None


def test_groupby_sink_priced_into_plan(planner):
    opt = JoinOrderOptimizer(planner)
    plain = make_star_query(2048, [256], seed=21)
    grouped = make_star_query(2048, [256], seed=21, group_by=("F.g",))
    p0, p1 = opt.optimize(plain), opt.optimize(grouped)
    assert p1.agg_plan is not None and p0.agg_plan is None
    assert p1.est_total_s > p0.est_total_s
    assert "group by" in p1.describe()
    assert p1.to_dict()["agg_scheme"] == p1.agg_plan.scheme


# ---------------------------------------------------------------------------
# Property: any group_by query matches reference_execute (hypothesis when
# available, deterministic sweep otherwise).
# ---------------------------------------------------------------------------

def _check_groupby_property(fact, dims, sel, kind, agg, seed):
    kinds = [kind] + ["inner"] * (len(dims) - 1)
    q = make_star_query(fact, dims,
                        selectivities=[sel] + [None] * (len(dims) - 1),
                        seed=seed, join_kinds=kinds, group_by=("F.g",),
                        aggregate=agg)
    ref_rows, _ = reference_execute(q)
    res, _ = run_pipeline(q, num_workers=0)
    got = res.rows_array()
    assert got.shape == ref_rows.shape and (got == ref_rows).all()


def test_property_groupby_matches_reference():
    try:
        from hypothesis import given, settings, strategies as st
    except ImportError:
        for fact, dims, sel, kind, agg, seed in (
                (512, [64], None, "inner", ("count",), 0),
                (1024, [64, 128], 0.3, "semi", ("sum", "F.m"), 1),
                (2048, [256], 0.5, "anti", ("max", "F.m"), 2),
                (512, [64, 64], None, "left_outer", ("avg", "F.m"), 3),
                (1024, [256], 0.05, "semi", ("min", "F.m"), 4)):
            _check_groupby_property(fact, dims, sel, kind, agg, seed)
        return

    @settings(max_examples=10, deadline=None)
    @given(fact=st.sampled_from([512, 1024, 2048]),
           dims=st.lists(st.sampled_from([64, 128, 256]), min_size=1,
                         max_size=2),
           sel=st.sampled_from([None, 0.05, 0.5]),
           kind=st.sampled_from(["inner", "semi", "anti", "left_outer"]),
           agg=st.sampled_from([("count",), ("sum", "F.m"),
                                ("min", "F.m"), ("avg", "F.m")]),
           seed=st.integers(0, 99))
    def check(fact, dims, sel, kind, agg, seed):
        _check_groupby_property(fact, dims, sel, kind, agg, seed)

    check()


# ---------------------------------------------------------------------------
# Analytic workload mix.
# ---------------------------------------------------------------------------

def test_workload_analytic_queries():
    from repro.engine import WorkloadGenerator
    gen = WorkloadGenerator(1024, seed=31)
    qs = [gen.analytic() for _ in range(4)]
    kinds = {j.kind for q in qs for j in q.joins}
    assert kinds - {"inner"}                 # variants actually appear
    assert all(q.group_by == ("F.g",) for q in qs)
    aggs = {q.aggregate[0] for q in qs}
    assert len(aggs) > 1                     # the aggregate cycle cycles
    gen2 = WorkloadGenerator(1024, seed=31)
    assert [q.describe() for q in qs] == \
        [gen2.analytic().describe() for _ in range(4)]


def test_workload_analytic_executes_correctly():
    from repro.engine import WorkloadGenerator
    gen = WorkloadGenerator(512, seed=37)
    q = gen.analytic()
    _run_vs_reference(q)
