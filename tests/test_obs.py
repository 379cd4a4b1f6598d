"""Observability: span nesting, Chrome export schema, registry thread
safety, and the predicted-vs-measured cost-model audit.

Tracer tests run on an injected fake clock — fully deterministic; the
service-level tests drive real joins through ``JoinQueryService`` and
validate the trace/metrics/audit the execution left behind.
"""
import importlib.util
import os
import threading

import pytest

from repro.core import (CoProcessor, Relation, join_oracle, uniform_relation,
                        unique_relation)
from repro.engine import (JoinQuery, JoinQueryService, QueryPlanner, Tenant)
from repro.obs import (CostAudit, MetricsRegistry, NULL_TRACER, NullTracer,
                       Tracer)


@pytest.fixture(scope="module")
def cp():
    return CoProcessor()


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _tiny_query(qid=1, **kw):
    b = unique_relation(256, seed=1)
    s = uniform_relation(256, key_range=256, seed=2)
    return JoinQuery(build=b, probe=s, query_id=qid, **kw)


# ---------------------------------------------------------------------------
# Tracer: nesting, ambient attributes, lanes, the no-op recorder.
# ---------------------------------------------------------------------------
def test_spans_nest_and_inherit_ambient_attrs_fake_clock():
    clk = FakeClock()
    tr = Tracer(clock=clk)
    with tr.span("query", q_key=7, tenant="gold") as q:
        clk.t = 1.0
        with tr.span("plan"):
            clk.t = 2.0
        q.set(scheme="CG_ss")          # discovered mid-span by planning
        with tr.span("probe", n=99):
            clk.t = 5.0
        clk.t = 6.0
    by_name = {s.name: s for s in tr.spans()}
    assert by_name["query"].t0 == 0.0 and by_name["query"].t1 == 6.0
    assert (by_name["plan"].t0, by_name["plan"].t1) == (1.0, 2.0)
    # Children inherit the ambient keys from the innermost open ancestor —
    # including attributes set mid-span *before* the child opened.
    assert by_name["plan"].attrs["q_key"] == 7
    assert by_name["plan"].attrs["tenant"] == "gold"
    assert "scheme" not in by_name["plan"].attrs
    assert by_name["probe"].attrs["scheme"] == "CG_ss"
    assert by_name["probe"].attrs["n"] == 99
    # Per-query index serves exactly the spans stamped with the key.
    assert {d["name"] for d in tr.spans_for(7)} == {"query", "plan", "probe"}


def test_span_stacks_are_per_thread():
    clk = FakeClock()
    tr = Tracer(clock=clk)
    ready = threading.Event()
    release = threading.Event()

    def worker():
        with tr.span("inner-w", q_key=2):
            ready.set()
            release.wait(10.0)

    with tr.span("outer-main", q_key=1):
        th = threading.Thread(target=worker, name="w0")
        th.start()
        ready.wait(10.0)
        release.set()
        th.join()
    spans = {s.name: s for s in tr.spans()}
    # The worker's span did NOT nest under (or inherit from) main's open
    # span: stacks are thread-local.
    assert spans["inner-w"].attrs["q_key"] == 2
    assert spans["inner-w"].thread == "w0"
    assert spans["outer-main"].thread != "w0"


def test_lane_records_cross_thread_interval_and_clamps():
    tr = Tracer(clock=FakeClock())
    tr.lane("queue", 1.0, 3.0, q_key=4)
    tr.lane("queue", 5.0, 2.0)          # inverted -> clamped to zero-length
    a, b = tr.spans()
    assert a.lane == "queue" and (a.t0, a.t1) == (1.0, 3.0)
    assert b.t1 == b.t0 == 5.0


def test_null_tracer_records_nothing():
    tr = NullTracer()
    with tr.span("x") as sp:
        assert sp is None
    tr.lane("queue", 0.0, 1.0)
    tr.instant("shed")
    assert tr.spans() == [] and tr.chrome_trace() == []
    assert NULL_TRACER.spans() == []


def test_tracer_bounds_span_count():
    tr = Tracer(clock=FakeClock(), max_spans=3)
    for i in range(5):
        with tr.span(f"s{i}"):
            pass
    assert len(tr.spans()) == 3


def test_tracer_past_cap_keeps_newest_and_per_query_traces():
    """Past ``max_spans`` the ring evicts its oldest spans, and per-query
    traces go on being indexed: a long-running service keeps them."""
    tr = Tracer(clock=FakeClock(), max_spans=4)
    for key in range(1, 6):
        with tr.span("query", q_key=key):
            with tr.span("plan"):
                pass
    assert [(s.name, s.attrs["q_key"]) for s in tr.spans()] == [
        ("plan", 4), ("query", 4), ("plan", 5), ("query", 5)]
    for key in range(1, 6):
        assert [s["name"] for s in tr.spans_for(key)] == ["plan", "query"]


def test_span_interval_excludes_its_annotation(monkeypatch):
    """A span's interval is read inside its profiler annotation: entering
    and leaving the annotation fall outside ``t0``..``t1``."""
    import repro.obs.trace as trace_mod
    clock = FakeClock()

    class Costly:
        def __init__(self, name):
            pass

        def __enter__(self):
            clock.t += 5.0

        def __exit__(self, *exc):
            clock.t += 7.0
            return False

    monkeypatch.setattr(trace_mod, "TraceAnnotation", Costly)
    tr = Tracer(clock=clock)
    with tr.span("plan"):
        clock.t += 1.0
    (rec,) = tr.spans()
    assert rec.t1 - rec.t0 == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# Chrome trace-event export.
# ---------------------------------------------------------------------------
def _validate_chrome(events):
    """Schema invariants Perfetto relies on: metadata first, timestamps
    sorted and non-negative, X slices properly nested per tid, async
    b/e pairs matched."""
    assert events
    n_meta = 0
    while n_meta < len(events) and events[n_meta]["ph"] == "M":
        n_meta += 1
    meta, rest = events[:n_meta], events[n_meta:]
    assert meta, "thread_name metadata missing"
    assert all(e["ph"] != "M" for e in rest)
    ts = [e["ts"] for e in rest]
    assert all(t >= 0 for t in ts)
    assert ts == sorted(ts)
    named_tids = {e["tid"] for e in meta}
    stacks: dict[int, list] = {}
    begins: dict[int, float] = {}
    for e in rest:
        assert e["pid"] == 1 and e["tid"] in named_tids
        if e["ph"] == "X":
            assert e["dur"] >= 0
            st = stacks.setdefault(e["tid"], [])
            while st and st[-1] <= e["ts"]:
                st.pop()
            for open_end in st:   # every open ancestor contains this span
                assert open_end >= e["ts"] + e["dur"]
            st.append(e["ts"] + e["dur"])
        elif e["ph"] == "b":
            begins[e["id"]] = e["ts"]
        elif e["ph"] == "e":
            assert e["ts"] >= begins.pop(e["id"])
        else:
            raise AssertionError(f"unexpected phase {e['ph']!r}")
    assert not begins, "unclosed async lane intervals"


def test_chrome_trace_schema_fake_clock(tmp_path):
    import json
    clk = FakeClock()
    tr = Tracer(clock=clk)
    clk.t = 10.0                       # non-zero epoch: ts must re-zero
    with tr.span("query", q_key=1):
        with tr.span("plan"):
            clk.t = 11.0
        clk.t = 12.0
    tr.lane("queue", 10.5, 11.5, q_key=1)
    events = tr.chrome_trace()
    _validate_chrome(events)
    # Parent precedes child at the shared start timestamp.
    xs = [e for e in events if e.get("ph") == "X"]
    assert [e["name"] for e in xs] == ["query", "plan"]
    path = tmp_path / "trace.json"
    tr.write_chrome_trace(path)
    with open(path) as f:
        payload = json.load(f)
    assert payload["traceEvents"] == events


def test_chrome_trace_from_live_service(cp):
    svc = JoinQueryService(cp=cp, planner=QueryPlanner(delta=0.25),
                           num_workers=2)
    with svc:
        handles = [svc.submit(_tiny_query(qid=i)) for i in range(4)]
        outs = [h() for h in handles]
        root = svc.submit_deferred(lambda o: _tiny_query(qid=10))
        child = svc.submit_deferred(lambda o: _tiny_query(qid=11),
                                    deps=[root])
        outs += [root(), child()]
    events = svc.tracer.chrome_trace()
    _validate_chrome(events)
    names = {e["name"] for e in events if e["ph"] in ("X", "b")}
    # The lifecycle stages all made it into the export.
    assert {"admit", "queue", "query", "plan", "probe"} <= names
    # Every submitted query carries the structured per-outcome trace,
    # and its spans share one correlation key.
    for out in outs:
        assert out.trace, f"query {out.query_id} missing trace"
        keys = {d["attrs"].get("q_key") for d in out.trace}
        assert len(keys) == 1 and None not in keys
        assert {"query", "plan"} <= {d["name"] for d in out.trace}


def test_queue_wait_becomes_async_lane_span(cp):
    svc = JoinQueryService(cp=cp, planner=QueryPlanner(delta=0.25),
                           num_workers=0)
    svc._ensure_workers = lambda: None
    q = _tiny_query(qid=3)
    svc.submit(q, block=False)
    qq, enq, _box, _done = svc._queue.get_nowait()
    out = svc.execute(qq, enqueued_at=enq)
    lanes = [d for d in out.trace if d["lane"] == "queue"]
    assert len(lanes) == 1 and lanes[0]["name"] == "queue"
    assert lanes[0]["dur_s"] >= 0.0
    # The lane shares the query's correlation key with its thread spans.
    assert lanes[0]["attrs"]["q_key"] == \
        out.trace[-1]["attrs"]["q_key"]


def test_disabled_tracer_leaves_no_outcome_trace(cp):
    svc = JoinQueryService(cp=cp, planner=QueryPlanner(delta=0.25),
                           num_workers=0, tracer=NULL_TRACER)
    out = svc.execute(_tiny_query(qid=1))
    assert out.trace is None
    assert svc.tracer.spans() == []
    # Metrics and the audit still work with tracing off.
    assert svc.stats()["completed"] == 1
    assert svc.audit.summary()["count"] > 0


# ---------------------------------------------------------------------------
# MetricsRegistry: thread safety, flat snapshots, collectors, events.
# ---------------------------------------------------------------------------
def test_registry_concurrent_increments_are_exact():
    reg = MetricsRegistry()
    n_threads, n_incs = 8, 2000

    def hammer(i):
        for _ in range(n_incs):
            reg.inc("ops", tenant=f"t{i % 2}")
            reg.inc("bytes", 3)

    threads = [threading.Thread(target=hammer, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert reg.counter_value("ops") == n_threads * n_incs
    assert reg.counter_value("bytes") == 3 * n_threads * n_incs
    snap = reg.snapshot()
    assert snap["ops"] == n_threads * n_incs
    assert snap["ops{tenant=t0}"] + snap["ops{tenant=t1}"] == snap["ops"]


def test_registry_snapshot_histograms_gauges_events_collectors():
    reg = MetricsRegistry()
    for v in range(1, 101):
        reg.observe("lat_s", v / 100.0)
    reg.set_gauge("depth", 4)
    reg.event("admission", action="shed", tenant="t", reason="deadline")
    reg.event("admission", action="degrade", tenant="t")
    reg.register_collector("cache", lambda: {"hit_rate": 0.5})
    reg.register_collector("broken", lambda: 1 / 0)
    snap = reg.snapshot()
    h = snap["lat_s"]
    assert h["count"] == 100 and h["min"] == 0.01 and h["max"] == 1.0
    assert h["p50"] == pytest.approx(0.50, abs=0.02)
    assert h["p95"] == pytest.approx(0.95, abs=0.02)
    assert snap["depth"] == 4
    assert snap["cache"] == {"hit_rate": 0.5}
    assert snap["broken"] is None      # a broken collector must not sink it
    sheds = [e for e in reg.events("admission")
             if e.get("action") == "shed"]
    assert sheds == [{"event": "admission", "action": "shed",
                      "tenant": "t", "reason": "deadline"}]


def test_service_stats_is_one_coherent_snapshot(cp):
    svc = JoinQueryService(cp=cp, planner=QueryPlanner(delta=0.25),
                           num_workers=0,
                           tenants=[Tenant("gold"), Tenant("bronze")])
    svc._ensure_workers = lambda: None
    for i, tenant in enumerate(("gold", "gold", "bronze")):
        svc.submit(_tiny_query(qid=i, tenant=tenant), block=False)
        qq, enq, _b, _d = svc._queue.get_nowait()
        svc.execute(qq, enqueued_at=enq)
    st = svc.stats()
    assert st["admitted"] == st["completed"] == 3
    assert st["tenants"]["gold"]["completed"] == 2
    assert st["tenants"]["bronze"]["admitted"] == 1
    # Component views ride in the same pass.
    assert st["cache"] is not None and st["planner"] is not None
    assert st["metrics"]["prediction_error"]["count"] > 0
    # The attribute API still reads the registry.
    assert svc.completed == 3 and svc.admitted == 3


def test_shed_emits_structured_admission_event(cp):
    svc = JoinQueryService(cp=cp, planner=QueryPlanner(delta=0.25),
                           num_workers=0,
                           tenants=[Tenant("t", deadline_s=0.01)])
    svc._ensure_workers = lambda: None
    svc._admission_estimate = lambda q: (10.0, 0.5)
    svc._degraded_estimate = lambda q: None
    from repro.engine import Backpressure
    with pytest.raises(Backpressure):
        svc.submit(_tiny_query(qid=9, tenant="t"), block=False)
    evs = svc.metrics.events("admission")
    assert len(evs) == 1
    ev = evs[0]
    assert ev["action"] == "shed" and ev["reason"] == "deadline"
    assert ev["tenant"] == "t" and ev["query_id"] == 9
    assert ev["retry_after_s"] > 0 and ev["predicted_s"] == 10.0
    assert ev["deadline_s"] is not None
    # ... and an instant marker in the trace, inside the admit span.
    names = [s.name for s in svc.tracer.spans()]
    assert names == ["shed", "admit"]


# ---------------------------------------------------------------------------
# Cost-model audit: est_s must come from the EXECUTED plan.
# ---------------------------------------------------------------------------
def test_audit_summary_percentiles():
    audit = CostAudit()
    for m in (1.0, 2.0, 3.0):
        audit.record([("probe", "CG_ss", 1.0, m)], tenant="gold")
    audit.record([("probe", "CG_ss", 0.0, 1.0)])   # est<=0 -> no ratio
    s = audit.summary()
    assert s["count"] == 4
    assert s["phases"]["probe"]["count"] == 3
    assert s["phases"]["probe"]["p50"] == pytest.approx(2.0)
    assert s["tenants"]["gold"]["p95"] == pytest.approx(3.0)


def test_audit_est_matches_executed_degraded_plan(cp):
    """Regression: the audit must price the plan the executor RAN — for a
    deadline-degraded query that is the cheapest plan, not the 10s
    admission-time estimate that triggered the degrade."""
    svc = JoinQueryService(cp=cp, planner=QueryPlanner(delta=0.25),
                           num_workers=0,
                           tenants=[Tenant("t", deadline_s=0.5)])
    svc._ensure_workers = lambda: None
    svc._admission_estimate = lambda q: (10.0, 0.5)
    svc._degraded_estimate = lambda q: 1e-4
    q = _tiny_query(qid=21, tenant="t")
    svc.submit(q, block=False)
    assert q.degraded is True
    qq, _enq, _box, _done = svc._queue.get_nowait()
    out = svc.execute(qq)
    recs = [r for r in svc.audit.records() if r["query_id"] == 21]
    assert recs, "executed query left no audit records"
    pairs = QueryPlanner.phase_pairs(out.plan, out.timing)
    assert [(r["phase"], r["scheme"]) for r in recs] == \
        [(p, s) for p, s, _, _ in pairs]
    for rec, (_, _, est_s, measured_s) in zip(recs, pairs):
        assert rec["est_s"] == pytest.approx(est_s)
        assert rec["measured_s"] == pytest.approx(measured_s)
        assert rec["est_s"] < 10.0      # NOT the admission-time estimate
        assert rec["tenant"] == "t"
    # The measured side is the real executed phase time.
    assert {r["phase"] for r in recs} <= set(out.timing.phase_s)


# ---------------------------------------------------------------------------
# One clock with the device trace; compile attribution; host syncs.
# ---------------------------------------------------------------------------
BENCH_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench")


def _bench_module(rel_path: str, name: str):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH_DIR, rel_path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _phj_planner():
    # Tiny cache + harsh random-access penalty: PHJ wins at 4k tuples.
    return QueryPlanner(delta=0.25, cache_bytes=1 << 10, rand_penalty=8.0,
                        phj_overhead_s=0.0)


def _phj_query(n_build=4096, n_probe=4096, qid=1):
    b = uniform_relation(n_build, seed=31)
    s = uniform_relation(n_probe, key_range=n_build, seed=32)
    return JoinQuery(build=b, probe=s, query_id=qid,
                     max_out=4 * n_probe + 1024)


def test_span_holds_profiler_annotation_null_tracer_none(monkeypatch):
    import repro.obs.trace as trace_mod
    entered = []

    class Recorder:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            entered.append(self.name)

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(trace_mod, "TraceAnnotation", Recorder)
    with NULL_TRACER.span("query"), Tracer(enabled=False).span("plan"):
        pass
    assert entered == []
    with Tracer(clock=FakeClock()).span("exchange", group="C") as sp:
        sp.set(bytes=8)
    assert entered == ["repro.exchange"]      # no attributes in the name


def test_profiler_trace_holds_program_spans_in_window(tmp_path):
    """A real profiler trace of one PHJ service join on the CPU: the
    program's spans share the profiler's clock with the harness's
    ``bench.window``, under stable ``repro.*`` names."""
    import jax
    reduce = _bench_module("trace_reduce.py", "obs_test_trace_reduce")
    svc = JoinQueryService(cp=CoProcessor(), planner=_phj_planner(),
                           num_workers=0)
    q = _phj_query()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            out = svc.execute(q)
            jax.block_until_ready(out.result)
    finally:
        jax.profiler.stop_trace()
    svc.close()
    assert out.plan.algorithm == "phj"
    planes = reduce.load_xplane(reduce.find_xplane(str(tmp_path)))
    lo, hi = reduce.window_of(planes)
    inside = {n for (n, s, d, _) in reduce.host_events(planes)
              if n.startswith("repro.") and lo <= s and s + d <= hi}
    assert {"repro.query", "repro.partition.side",
            "repro.exchange"} <= inside
    assert {"repro.launch", "repro.d2h", "repro.h2d",
            "repro.join.collect"} <= inside


def test_first_launch_carries_compiles_repeat_does_not():
    svc = JoinQueryService(cp=CoProcessor(), planner=_phj_planner(),
                           num_workers=0)
    q = _phj_query(4000, 3000)

    def launches(outcome):
        return [s for s in outcome.trace if s["name"] == "launch"
                and s["attrs"]["program"] == "phj_join"]

    first = svc.execute(q)
    assert first.plan.algorithm == "phj"
    assert launches(first)
    assert all(s["attrs"]["compile_s"] > 0 and s["attrs"]["compiles"] >= 1
               for s in launches(first))
    again = svc.execute(JoinQuery(build=q.build, probe=q.probe, query_id=2,
                                  max_out=q.max_out))
    assert launches(again)
    assert not any("compile_s" in s["attrs"] or "compiles" in s["attrs"]
                   for s in launches(again))
    # The launch span is the one per-program record: no registry copy.
    assert not any(k.startswith(("compiles", "compile_s"))
                   for k in svc.metrics.snapshot())
    svc.close()


def _share_rungs(metrics) -> dict:
    """``{group + side: (rung, count)}`` of the ``phj_share_rung`` series."""
    out = {}
    for labels, v in metrics.counter_series("phj_share_rung").items():
        lab = dict(labels)
        out[lab["group"] + lab["side"]] = (lab["rung"], v)
    return out


def test_fresh_relations_reuse_the_join_program():
    """Shares are shaped by a capacity fixed before the data, so a second
    query on fresh relations of the same sizes compiles no ``phj_join``."""
    n = 1 << 15
    svc = JoinQueryService(cp=CoProcessor(), planner=_phj_planner(),
                           num_workers=0)

    def run(qid, seed):
        b = uniform_relation(n, seed=seed)
        s = uniform_relation(n, key_range=n, seed=seed + 1)
        out = svc.execute(JoinQuery(build=b, probe=s, query_id=qid,
                                    max_out=4 * n + 1024))
        assert out.plan.algorithm == "phj"
        assert (out.result.valid_pairs() == join_oracle(b, s)).all()
        return out

    run(1, seed=51)
    before = svc.metrics.counter_value("phj_share_rung")
    again = run(2, seed=61)
    launches = [s for s in again.trace if s["name"] == "launch"
                and s["attrs"]["program"] == "phj_join"]
    assert launches
    assert not any("compiles" in s["attrs"] for s in launches)
    exchanges = [s["attrs"] for s in again.trace if s["name"] == "exchange"]
    assert len(exchanges) == len(launches)
    for a in exchanges:
        for side in ("R", "S"):
            assert 0 < a[f"rows_{side}"] <= a[f"capacity_{side}"]
    # One count per group and side that owns partitions, all on rung 0.
    assert svc.metrics.counter_value("phj_share_rung") - before == \
        2 * len(exchanges)
    rungs = _share_rungs(svc.metrics)
    assert {r for r, _ in rungs.values()} == {0}
    assert all(v == 2 for _, v in rungs.values())
    svc.close()


def test_hot_key_share_climbs_a_rung_and_stays_exact():
    """Half the build rows on key 0 (partition 0, owned by C): C's share
    outgrows the base rung, climbs past it, and the answer stays exact."""
    n = 1 << 15
    b = uniform_relation(n, seed=71)
    b = Relation(b.rid, b.key.at[: n // 2].set(0))
    s = uniform_relation(n, key_range=n, seed=72)
    exp = join_oracle(b, s)
    svc = JoinQueryService(cp=CoProcessor(), planner=_phj_planner(),
                           num_workers=0)
    out = svc.execute(JoinQuery(build=b, probe=s, query_id=1,
                                max_out=len(exp) + 1024))
    assert out.plan.algorithm == "phj" and 0 < out.plan.join_ratio < 1
    assert (out.result.valid_pairs() == exp).all()
    rungs = _share_rungs(svc.metrics)
    assert rungs["CR"][0] >= 1
    assert rungs["CS"][0] == rungs["GR"][0] == rungs["GS"][0] == 0
    svc.close()


def test_phj_phase_keys_stay_partition_and_join():
    """The new spans are tracer-only: ``coprocess_ms`` sums ``phase_s``,
    whose PHJ keys stay exactly these two."""
    cp = CoProcessor(tracer=Tracer())
    q = _phj_query(2048, 2048)
    _, timing = cp.phj(q.build, q.probe, schedule=(3, 2), shj_bits=2,
                       max_out=q.max_out, partition_ratio=0.5,
                       join_ratio=0.5)
    assert set(timing.phase_s) == {"partition", "join"}
    assert not hasattr(timing, "merge_s")
    names = {s.name for s in cp.tracer.spans()}
    assert {"partition.side", "exchange", "launch", "join.collect", "d2h",
            "h2d"} <= names


def test_host_syncs_count_each_pull_once():
    metrics = MetricsRegistry()
    cp = CoProcessor(tracer=Tracer())
    cp.metrics = metrics
    q = _phj_query(2048, 2048)
    cp.phj(q.build, q.probe, schedule=(3, 2), shj_bits=2, max_out=q.max_out,
           partition_ratio=0.5, join_ratio=0.5)
    pulls: dict = {}
    for s in cp.tracer.spans():
        if s.name == "d2h":
            pulls[s.attrs["site"]] = pulls.get(s.attrs["site"], 0) + 1
    # Two sides x two groups for partition pieces and pid masks; each
    # side's rows once (both groups slice one host copy); one result per
    # group.
    assert pulls == {"phj.partition": 4, "phj.exchange.mask": 4,
                     "phj.exchange.rows": 2, "phj.join.collect": 2}
    snap = metrics.snapshot()
    assert {site: snap[f"host_syncs{{site={site}}}"] for site in pulls} \
        == pulls
    assert snap["host_syncs"] == sum(pulls.values())


def test_standalone_coprocessor_records_nothing():
    cp = CoProcessor()
    q = _phj_query(2048, 2048)
    cp.phj(q.build, q.probe, schedule=(3, 2), shj_bits=2, max_out=q.max_out,
           partition_ratio=0.5, join_ratio=0.5)
    assert cp.tracer is NULL_TRACER and NULL_TRACER.spans() == []
    assert cp.ledger is None and cp.metrics is None


@pytest.mark.parametrize("completed,compile_s,want", [
    (2, 2.5, 1250.0), (1, 0.0, 0.0), (0, 3.0, None)])
def test_compile_ms_metric_reads_window_compile_seconds(completed,
                                                        compile_s, want):
    from types import SimpleNamespace
    mod = _bench_module("metrics/compile_ms.py", "obs_test_compile_ms")
    r = SimpleNamespace(completed=completed,
                        compiles=SimpleNamespace(compile_s=compile_s))
    assert mod.UNIT == "ms"
    got = mod.read(r)
    assert got == (None if want is None else pytest.approx(want))
