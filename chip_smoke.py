#!/usr/bin/env python3
"""On-chip smoke run of the join engine's serving path.

Drives ``JoinQueryService`` -> ``CoProcessor`` (and ``PipelineExecutor`` for
the star query) once at the paper's data scale on a TPU, checks every result
row for row against the NumPy references, and fails unless every query ran
on the device path: no failed query, no reference-path answer, no retry, no
breaker short-circuit, no recovery event.

    python chip_smoke.py [--seed N]          # one chip: phases k, a, b, c
    python chip_smoke.py --chips 4 [--seed N]

One chip (the default): k) the main-path Pallas kernels, lowered and run
against their jnp paths; a) a cold 16M x 16M uniform equi-join, with the
operator's view of it: the service's span totals by name (with the
compile seconds inside them), its transfer-ledger bytes by cause and its
host syncs by site; b) a mixed
stream of joins over 64k-1M tuple relations with hot-table repeats; c) a
3-join star over a 16M-row fact table whose filtered dimensions leave the
group-by + sum sink few enough rows for the aggregation kernel.

``--chips 4``: only the co-processing placement path, all DD-planned on
the default C = 1 / G = 3 chip split: the main-path kernels under
``shard_map`` over the three-chip G mesh against the same kernels on one
chip, one join from (b) against the same join on one chip (C and G the
same chip) and the NumPy oracle, and phase (a) against the oracle.

The compile cache goes where ``JAX_COMPILATION_CACHE_DIR`` says, else to
``.jax_cache/`` in this checkout.  The wall times printed are single smoke
runs, not benchmark numbers.  The last line of standard output is one JSON
object: ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from functools import partial

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

PAPER_TUPLES = 1 << 24            # paper §5.1: 16M tuples per relation
# (base tuples, queries): the mixed generator draws 0.5x/1x/2x its base,
# so the two streams cover 64k..256k and 256k..1M tuples.  Every new
# relation size compiles its own sort programs (about 10 s each on a v5e
# host), so the stream is kept short enough for a cold run to finish
# inside 1200 s.
MIXED_STREAMS = ((1 << 17, 6), (1 << 19, 3))
STAR_FACT_ROWS = 1 << 24
STAR_DIM_ROWS = 1 << 16
# Dimension filters that leave about 8k of the 16M fact rows for the
# group-by sink, inside the segmented-aggregation kernel's slot gate.
STAR_SELECTIVITIES = (0.1, 0.1, 0.05)
KERNEL_TUPLES = 1 << 17           # fused n1+n2 at its size gate
KERNEL_BITS = 8
AGG_SLOTS = 1 << 14               # segmented aggregation at its slot gate


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, read per phase
    through ``take``.  A program loaded from the persistent cache still
    reports a backend compile (the load), and also counts as a hit."""

    def __init__(self):
        import jax
        self.total = 0.0
        self.backend = 0.0
        self.programs = 0
        self.cache_hits = 0
        self._mark = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **_):
        if name.startswith("/jax/core/compile/"):
            self.total += secs
            if name.endswith("backend_compile_duration"):
                self.backend += secs
                self.programs += 1
        elif name == "/jax/compilation_cache/cache_retrieval_time_sec":
            self.cache_hits += 1

    def take(self) -> float:
        d, self._mark = self.total - self._mark, self.total
        return d


def block(tree) -> None:
    import jax
    jax.block_until_ready([x for x in jax.tree.leaves(tree)
                           if isinstance(x, jax.Array)])


def phase_line(name, wall_s, rows, plans, compile_s, **extra) -> None:
    more = "".join(f" {k}={v}" for k, v in extra.items())
    print(f"phase {name}: smoke_wall_s={wall_s:.3f} rows={rows} "
          f"plan={plans} compile_s={compile_s:.2f}{more}", flush=True)


def plan_str(outcomes) -> str:
    seen: dict = {}
    for o in outcomes:
        k = f"{o.plan.algorithm}/{o.plan.scheme}"
        seen[k] = seen.get(k, 0) + 1
    return ",".join(f"{k}x{v}" for k, v in sorted(seen.items()))


def np_equal(a, b) -> bool:
    return a.shape == b.shape and bool((a == b).all())


# ---------------------------------------------------------------------------
# Phases.
# ---------------------------------------------------------------------------

def phase_kernels(seed: int, clock: CompileClock, mesh=None,
                  place=None) -> None:
    """Lower each main-path kernel's dispatcher at a smoke shape, require
    the Mosaic custom call in it, and run it against its jnp path."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import uniform_relation
    from repro.kernels.agg.ops import segmented_aggregate
    from repro.kernels.partition_hist.ops import fused_partition_pass

    place = place or (lambda x: x)
    # Whole (8, 128) tiles on every shard, at or just below each gate.
    tile = 1024 * (1 if mesh is None else mesh.size)
    n_part = KERNEL_TUPLES // tile * tile
    n_agg = AGG_SLOTS // tile * tile
    rng = np.random.default_rng(seed + 7)
    rel = uniform_relation(n_part, seed=seed + 7)
    gid = jnp.asarray(rng.integers(-1, AGG_SLOTS, n_agg, dtype=np.int32))
    val = jnp.asarray(rng.integers(-2**31, 2**31 - 1, n_agg,
                                   dtype=np.int32))
    cases = [("fused_partition_pass", fused_partition_pass, (rel,),
              dict(shift=0, bits=KERNEL_BITS))]
    for wrap32 in (False, True):
        cases.append((f"segmented_aggregate[wrap32={wrap32}]",
                      segmented_aggregate, (gid, val),
                      dict(num_slots=AGG_SLOTS, wrap32=wrap32)))
    t0 = time.perf_counter()
    for name, fn, args, kw in cases:
        kernel = jax.jit(partial(fn, mesh=mesh, **kw))
        placed = place(args)
        if "tpu_custom_call" not in kernel.lower(*placed).as_text():
            fail(f"{name}: no tpu_custom_call in the lowered program "
                 f"(the dispatcher did not select its Pallas kernel)")
        got = kernel(*placed)
        exp = jax.jit(partial(fn, use_pallas=False, **kw))(*args)
        for g, e in zip(jax.tree.leaves(got), jax.tree.leaves(exp)):
            if not np.array_equal(np.asarray(g), np.asarray(e)):
                fail(f"{name}: Pallas kernel disagrees with its jnp path")
        print(f"kernel {name}: tpu_custom_call lowered, bit-exact vs jnp "
              f"path" + ("" if mesh is None else
                         f" (shard_map over {mesh.size} chips)"), flush=True)
    phase_line("k", time.perf_counter() - t0, n_part + 2 * n_agg,
               "kernels", clock.take())


def phase_paper_join(svc, seed: int, clock: CompileClock, *, tag: str,
                     expected=None):
    """(a) Cold uniform 16M x 16M equi-join, checked pair for pair."""
    from repro.core import join_oracle, uniform_relation
    from repro.engine import JoinQuery

    build = uniform_relation(PAPER_TUPLES, seed=seed)
    probe = uniform_relation(PAPER_TUPLES, key_range=PAPER_TUPLES,
                             seed=seed + 1)
    q = JoinQuery(build=build, probe=probe, tag="paper16M",
                  max_out=PAPER_TUPLES + PAPER_TUPLES // 4, query_id=1)
    clock.take()
    mark = (svc.tracer.now(), svc.ledger.by_cause(),
            svc.metrics.counter_series("host_syncs"))
    t0 = time.perf_counter()
    (o,) = svc.run([q])
    block(o.result)
    wall = time.perf_counter() - t0
    compile_s = clock.take()
    obs_lines(svc, tag, *mark)
    if expected is None:
        expected = join_oracle(build, probe)
    if not np_equal(o.result.valid_pairs(), expected):
        fail(f"phase {tag}: 16M join differs from join_oracle")
    print(f"phase {tag} plan: {o.plan.algorithm}/{o.plan.scheme} "
          f"schedule={list(o.plan.schedule or [])} "
          f"ratios=({[round(r, 3) for r in o.plan.build_ratios]}, "
          f"{[round(r, 3) for r in o.plan.probe_ratios]})",
          flush=True)
    phase_line(tag, wall, int(o.result.count), plan_str([o]), compile_s,
               pairs_checked=len(expected))
    return [o], expected


def obs_lines(svc, tag: str, since: float, ledger0: dict,
              syncs0: dict) -> None:
    """What the service recorded since ``since``: span totals by name
    (count, seconds, compile seconds inside), ledger MiB by cause, host
    syncs by site."""
    totals: dict = {}
    for r in svc.tracer.spans():
        if r.lane is None and r.t0 >= since:
            n, sec, comp = totals.get(r.name, (0, 0.0, 0.0))
            totals[r.name] = (n + 1, sec + r.t1 - r.t0,
                              comp + r.attrs.get("compile_s", 0.0))
    for name, (n, sec, comp) in sorted(totals.items(),
                                       key=lambda kv: -kv[1][1]):
        print(f"phase {tag} span {name}: count={n} total_s={sec:.3f} "
              f"compile_s={comp:.3f}", flush=True)
    by = svc.ledger.by_cause()
    print(f"phase {tag} ledger_mib: " + " ".join(
        f"{c}={(by[c] - ledger0.get(c, 0)) / (1 << 20):.3f}" for c in by),
        flush=True)
    syncs = svc.metrics.counter_series("host_syncs")
    print(f"phase {tag} host_syncs: " + " ".join(
        f"{dict(k)['site']}={int(v - syncs0.get(k, 0))}"
        for k, v in sorted(syncs.items()) if v > syncs0.get(k, 0)),
        flush=True)


def cache_hits(svc) -> int:
    c = svc.stats()["cache"]
    return c["hits"] + c["partition_hits"] + c["probe_partition_hits"]


def phase_mixed(svc, seed: int, clock: CompileClock):
    """(b) Mixed join stream, 64k..1M tuples, hot-table repeats."""
    from repro.core import join_oracle
    from repro.engine import make_workload

    queries = []
    for i, (base, count) in enumerate(MIXED_STREAMS):
        queries += make_workload("mixed", num_queries=count,
                                 base_tuples=base, seed=seed + 10 + i)
    for i, q in enumerate(queries):
        q.query_id = 100 + i
    clock.take()
    hits0 = cache_hits(svc)
    t0 = time.perf_counter()
    outcomes = svc.run(queries)
    block([o.result for o in outcomes])
    wall = time.perf_counter() - t0
    compile_s = clock.take()
    rows = 0
    for q, o in zip(queries, outcomes):
        if not np_equal(o.result.valid_pairs(),
                        join_oracle(q.build, q.probe)):
            fail(f"phase b: query {q.query_id} ({q.tag}, |R|={q.build.size},"
                 f" |S|={q.probe.size}) differs from join_oracle")
        rows += int(o.result.count)
    hot = sum(q.tag == "hot_table" for q in queries)
    hits = cache_hits(svc) - hits0
    if hot and hits == 0:
        fail("phase b: hot_table repeats never hit BuildTableCache")
    sizes = sorted({q.build.size for q in queries}
                   | {q.probe.size for q in queries})
    small_phj = sum(o.plan.algorithm == "phj"
                    and min(q.build.size, q.probe.size) <= KERNEL_TUPLES
                    for q, o in zip(queries, outcomes))
    phase_line("b", wall, rows, plan_str(outcomes), compile_s,
               queries=len(queries), hot_table=hot, cache_hits=hits,
               sizes=f"{sizes[0]}..{sizes[-1]}",
               phj_queries_le_128k=small_phj)
    return outcomes


def phase_star(svc, seed: int, clock: CompileClock):
    """(c) Fused 3-join star over a 16M-row fact table, group-by sink."""
    from repro.queries import (JoinOrderOptimizer, PipelineExecutor,
                               apply_group_by, make_star_query,
                               reference_rows, rows_array)

    query = make_star_query(STAR_FACT_ROWS, [STAR_DIM_ROWS] * 3,
                            selectivities=STAR_SELECTIVITIES,
                            seed=seed + 20, aggregate=("sum", "F.m"),
                            group_by=("F.g",))
    ex = PipelineExecutor(service=svc, handoff="device",
                          optimizer=JoinOrderOptimizer(svc.planner,
                                                       handoff="device"))
    clock.take()
    t0 = time.perf_counter()
    physical, res = ex.run_optimized(query)
    got = res.rows_array()
    wall = time.perf_counter() - t0
    compile_s = clock.take()
    joined = reference_rows(query)          # reference_execute, unrolled
    ref_rows = rows_array(apply_group_by(joined, query.group_by,
                                         query.aggregate))
    if not np_equal(got, ref_rows):
        fail("phase c: star group-by differs from the NumPy reference")
    sink_in = len(next(iter(joined.values())))
    if sink_in > AGG_SLOTS:
        fail(f"phase c: {sink_in} sink rows are past the aggregation "
             f"kernel's {AGG_SLOTS}-slot gate; the kernel was not served")
    print(f"phase c plan:\n{physical.describe()}", flush=True)
    phase_line("c", wall, res.rows, plan_str(res.outcomes), compile_s,
               fact_rows=STAR_FACT_ROWS, sink_input_rows=sink_in)
    return list(res.outcomes)


def check_device_path(svc, outcomes, label: str) -> None:
    """Fail if any query left the device path."""
    st = svc.stats()
    res = st["resilience"]
    ref = [o.query_id for o in outcomes if o.timing.notes.get("reference_path")]
    recov = svc.metrics.events("recovery")
    problems = []
    if st["failed"]:
        problems.append(f"{st['failed']} failed queries")
    if ref:
        problems.append(f"reference-path answers for queries {ref}")
    for k in ("retries", "breaker_short_circuits"):
        if res[k]:
            problems.append(f"{k}={res[k]}")
    if recov:
        problems.append(f"recovery events {recov[:3]}")
    if problems:
        fail(f"{label}: left the device path: " + "; ".join(problems))
    print(f"{label}: device path only: failed=0 reference_path=0 retries=0 "
          f"breaker_short_circuits=0 recovery_events=0 over "
          f"{len(outcomes)} outcomes", flush=True)


# ---------------------------------------------------------------------------
# Drivers.
# ---------------------------------------------------------------------------

def calibrated_planner(cp, **kw):
    from repro.engine import QueryPlanner

    t0 = time.perf_counter()
    planner = QueryPlanner.calibrated(cp, **kw)
    pp = planner.pass_planner
    units = " ".join(f"{k}=({c:.3e}, {g:.3e})"
                     for k, (c, g) in sorted(planner.u_overrides.items()))
    print(f"planner: calibrated on chip in {time.perf_counter() - t0:.1f} s;"
          f" partition s/item n1={pp.u_n1:.3e} n2={pp.u_n2:.3e} "
          f"n3={pp.u_n3:.3e}; handoff latency="
          f"{planner.handoff_latency_s:.3e} s bw="
          f"{planner.handoff_bw_bytes_per_s:.3e} B/s; "
          f"build/probe (C, G) s/item: {units}", flush=True)
    return planner


def one_chip(seed: int, clock: CompileClock) -> None:
    from repro.core import CoProcessor
    from repro.engine import JoinQueryService

    phase_kernels(seed, clock)
    cp = CoProcessor()
    planner = calibrated_planner(cp)
    clock.take()
    with JoinQueryService(cp=cp, planner=planner, num_workers=2) as svc:
        outcomes, _ = phase_paper_join(svc, seed, clock, tag="a")
        outcomes += phase_mixed(svc, seed, clock)
        outcomes += phase_star(svc, seed, clock)
        check_device_path(svc, outcomes, "one chip")


def watch_group_outputs(group, log: list) -> None:
    """Record the device set of every output of ``group``'s programs."""
    import jax
    jit = group.jit

    def spied(key, fn):
        f = jit(key, fn)

        def call(*a, **kw):
            out = f(*a, **kw)
            ids = {d.id for x in jax.tree.leaves(out)
                   if isinstance(x, jax.Array) for d in x.sharding.device_set}
            log.append((str(key[0]), tuple(sorted(ids))))
            return out
        return call
    group.jit = spied


def check_g_outputs(g_outputs: list) -> None:
    sets = sorted(set(g_outputs))
    for prog, ids in sets:
        print(f"G-group output: program={prog} device_set={list(ids)}",
              flush=True)
    if not sets or any(len(ids) != 3 for _, ids in sets):
        fail("G-group outputs did not all land on three chips")


def four_chips(seed: int, clock: CompileClock, devices) -> None:
    """Cheapest check first: kernels, then the DD join on both
    placements, then the 16M join on C1/G3."""
    from repro.core import CoProcessor, join_oracle
    from repro.engine import JoinQueryService, QueryPlanner, make_workload

    cp4 = CoProcessor()
    if (cp4.c.size, cp4.g.size) != (1, 3):
        fail(f"expected a C=1 / G=3 split, got C={cp4.c.size} "
             f"G={cp4.g.size}")
    cp1 = CoProcessor(c_devices=devices[:1], g_devices=devices[:1])
    print(f"groups: C={[d.id for d in cp4.c.devices]} "
          f"G={[d.id for d in cp4.g.devices]} vs one chip "
          f"{devices[0].id}", flush=True)
    phase_kernels(seed, clock, mesh=cp4.g.mesh, place=cp4.g.put_items)

    g_outputs: list = []
    watch_group_outputs(cp4.g, g_outputs)
    # Placement, not plan quality, is under test: every query is DD, so
    # both groups take a share.
    planner = QueryPlanner(allowed_schemes=("DD",))
    dd_query = make_workload("uniform", num_queries=1,
                             base_tuples=MIXED_STREAMS[0][0],
                             seed=seed + 10)[0]
    exp_dd = join_oracle(dd_query.build, dd_query.probe)
    with JoinQueryService(cp=cp4, planner=planner, num_workers=1) as svc4, \
            JoinQueryService(cp=cp1, planner=planner, num_workers=1) as svc1:
        outcomes = {}
        for name, svc in (("C1/G3", svc4), ("one chip", svc1)):
            clock.take()
            t0 = time.perf_counter()
            (o,) = svc.run([dd_query])
            block(o.result)
            wall = time.perf_counter() - t0
            if not np_equal(o.result.valid_pairs(), exp_dd):
                fail(f"DD query on {name} differs from join_oracle")
            phase_line(f"b_dd[{name}]", wall, int(o.result.count),
                       plan_str([o]), clock.take(),
                       size=f"{dd_query.build.size}x{dd_query.probe.size}")
            outcomes[name] = [o]
        check_g_outputs(g_outputs)
        print("placement: DD join on C1/G3 == one chip == join_oracle",
              flush=True)
        outcomes["C1/G3"] += phase_paper_join(svc4, seed, clock,
                                              tag="a[C1/G3]")[0]
        check_g_outputs(g_outputs)
        check_device_path(svc4, outcomes["C1/G3"], "C1/G3")
        check_device_path(svc1, outcomes["one chip"], "one chip")
    print("four chips: C1/G3 == one chip == join_oracle, G outputs on "
          "three chips", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    if not os.path.isdir(os.path.join(SRC, "repro")):
        fail(f"the repro package is not at {SRC}; run chip_smoke.py from "
             f"a checkout of the repository")
    sys.path.insert(0, SRC)
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"no TPU found: JAX's first device is {dev.platform!r} "
             f"({dev.device_kind}); this smoke run never falls back to "
             f"the CPU")
    if len(devices) < args.chips:
        fail(f"--chips {args.chips} needs {args.chips} chips, JAX sees "
             f"{len(devices)}")
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)

    from repro.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    clock = CompileClock()
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(args.seed, clock, devices)
    else:
        one_chip(args.seed, clock)
    print(f"total: smoke_wall_s={time.perf_counter() - t0:.3f} "
          f"compile_s={clock.total:.2f} backend_compile_s="
          f"{clock.backend:.2f} programs={clock.programs} "
          f"cache_hits={clock.cache_hits}",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
