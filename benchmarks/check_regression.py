"""Tiny perf regression gate over the BENCH_*.json rollup artifact.

Reads the newest ``reports/bench/BENCH_*.json``, extracts the smoke
query-pipeline and SLO figures, and fails (exit 1) when:

  * the fused path moved any intermediate bytes through the host
    (``host_bytes_moved`` must be 0 — the device-resident invariant), or
  * the smoke 3-join star end-to-end time regressed more than
    ``TOLERANCE`` (25%) past the committed baseline value, or
  * the smoke ``slo_bench`` deadline hit rate (cost mode) fell below the
    baseline floor, its shed rate rose above the baseline ceiling, or a
    shed query escaped without a structured ``Backpressure``, or
  * the observability plumbing went dark: the cost-model audit trail is
    empty or carries non-finite prediction-error percentiles for an
    executed phase, or the metrics registry's ``host_bytes_moved``
    disagrees with the fused-path figure the hand-off section reported, or
  * the observability loop stopped *acting*: the SLO burn-rate monitor
    fired at steady state (alert noise) or stayed silent through the
    bursty overload replay, the flight-recorder dump is missing or
    schema-invalid, or the ``cost_model_staleness`` gauge is absent or
    non-finite, or
  * the resilience layer stopped earning its keep: deadline preemption
    never fired (or made the gold hit rate worse) in the overload A/B,
    the chaos replay leaked an unstructured failure, produced a
    non-row-exact result, counted a hard failure, left a hung worker,
    or opened a breaker without a structured event on record, or
  * the data-path observability went dark or dishonest: the cardinality
    audit carries no (or non-finite) q-error summary for an executed
    stage type, the fused run's transfer ledger shows an unknown cause
    or any ``handoff`` bytes, the ledger's intermediate sum disagrees
    with the flat fused-path figure, or the adaptive skewed-star run
    failed to replan or to beat static execution.

The baseline lives in ``benchmarks/baseline.json``; refresh it (with a
note in the commit) whenever an intentional change moves the number.
The SLO bounds are deliberately loose — CI hosts are noisy and the smoke
run is small; the gate catches the admission layer breaking outright
(hit rate collapsing, shedding everything), not percentage drift.

    PYTHONPATH=src python -m benchmarks.check_regression
"""
from __future__ import annotations

import glob
import json
import math
import os
import sys

TOLERANCE = 1.25

HERE = os.path.dirname(__file__)
BASELINE_PATH = os.path.join(HERE, "baseline.json")
BENCH_GLOB = os.path.join(HERE, "..", "reports", "bench", "BENCH_*.json")


def main() -> int:
    rollups = sorted(glob.glob(BENCH_GLOB))
    if not rollups:
        print("check_regression: no BENCH_*.json rollup found", flush=True)
        return 1
    with open(rollups[-1]) as f:
        rollup = json.load(f)
    entry = rollup.get("benchmarks", {}).get("query_pipeline")
    if not entry or not entry.get("ok") or not entry.get("payload"):
        print(f"check_regression: no successful query_pipeline payload in "
              f"{rollups[-1]}", flush=True)
        return 1
    payload = entry["payload"]
    if not payload.get("smoke"):
        print("check_regression: rollup is not a smoke run; gate applies "
              "to CI smoke figures only — skipping", flush=True)
        return 0
    with open(BASELINE_PATH) as f:
        baseline = json.load(f)["query_pipeline"]

    failures = []
    fused_bytes = payload["handoff"]["host_bytes_moved_fused"]
    if fused_bytes != 0:
        failures.append(f"fused hand-off moved {fused_bytes} intermediate "
                        f"bytes through the host (want 0)")
    measured = payload["join_order"]["chosen_s"]
    allowed = baseline["smoke_star_chosen_s"] * TOLERANCE
    verdict = "OK" if measured <= allowed else "REGRESSED"
    print(f"check_regression: smoke star chosen order {measured:.3f}s "
          f"(baseline {baseline['smoke_star_chosen_s']:.3f}s, "
          f"allowed {allowed:.3f}s) -> {verdict}", flush=True)
    if measured > allowed:
        failures.append(f"smoke star end-to-end {measured:.3f}s exceeds "
                        f"{TOLERANCE:.2f}x baseline "
                        f"{baseline['smoke_star_chosen_s']:.3f}s")
    print(f"check_regression: fused intermediate host bytes = "
          f"{fused_bytes}", flush=True)

    # -- observability gate: audit trail populated, registry coherent -----
    snap = payload.get("metrics_snapshot") or {}
    audit = snap.get("prediction_error")
    if not audit or not audit.get("count"):
        failures.append("cost-model audit trail is empty "
                        "(metrics_snapshot.prediction_error missing)")
    else:
        shown = []
        for phase, s in sorted((audit.get("phases") or {}).items()):
            p50, p95 = s.get("p50"), s.get("p95")
            finite = all(isinstance(v, (int, float)) and math.isfinite(v)
                         for v in (p50, p95))
            if not s.get("count") or not finite:
                failures.append(f"prediction-error summary for phase "
                                f"'{phase}' is missing or non-finite: {s}")
            else:
                shown.append(f"{phase}: p50={p50:.2f} p95={p95:.2f}")
        if not audit.get("phases"):
            failures.append("cost-model audit has records but no "
                            "per-phase prediction-error summaries")
        print(f"check_regression: audit records={audit['count']}, "
              f"prediction-error ratios {{{'; '.join(shown)}}}", flush=True)
    reg_bytes = snap.get("host_bytes_moved")
    if reg_bytes != fused_bytes:
        failures.append(f"metrics registry host_bytes_moved={reg_bytes} "
                        f"disagrees with the fused hand-off figure "
                        f"{fused_bytes}")

    # -- data-path observability: cardinality audit present + finite ------
    KNOWN_CAUSES = ("fingerprint", "multicol_pack", "handoff", "result",
                    "exchange", "base_upload")
    INTERMEDIATE = ("fingerprint", "multicol_pack", "handoff")
    card = payload.get("cardinality") or {}
    if not card.get("count") or not card.get("stage_types"):
        failures.append("cardinality audit is empty (payload.cardinality "
                        "missing stage-type q-error summaries)")
    else:
        shown = []
        for stype, s in sorted(card["stage_types"].items()):
            p50, p95 = s.get("p50"), s.get("p95")
            finite = all(isinstance(v, (int, float)) and math.isfinite(v)
                         and v >= 1.0 for v in (p50, p95))
            if not s.get("count") or not finite:
                failures.append(f"cardinality q-error for stage type "
                                f"'{stype}' is missing or non-finite: {s}")
            else:
                shown.append(f"{stype}: p50={p50:.2f} p95={p95:.2f}")
        print(f"check_regression: cardinality records={card['count']}, "
              f"q-error {{{'; '.join(shown)}}}", flush=True)

    # -- data-path observability: ledger attribution exact + fused-quiet --
    ledger = payload.get("ledger") or {}
    by_cause = ledger.get("by_cause") or {}
    if not by_cause:
        failures.append("transfer ledger missing from payload")
    else:
        unknown = sorted(set(by_cause) - set(KNOWN_CAUSES))
        if unknown:
            failures.append(f"transfer ledger reports unknown cause(s) "
                            f"{unknown}")
        if by_cause.get("handoff", 0) != 0:
            failures.append(f"fused-path ledger shows "
                            f"{by_cause['handoff']} handoff bytes (want 0)")
        inter_sum = sum(by_cause.get(c, 0) for c in INTERMEDIATE)
        if inter_sum != fused_bytes:
            failures.append(f"ledger intermediate sum {inter_sum} "
                            f"disagrees with the fused hand-off figure "
                            f"{fused_bytes}")
        print(f"check_regression: ledger by_cause={by_cause} "
              f"(intermediate sum {inter_sum})", flush=True)

    # -- adaptive re-optimization must fire and win on the skewed star ----
    adaptive = payload.get("adaptive") or {}
    if not adaptive:
        failures.append("adaptive skewed-star section missing from payload")
    else:
        replans = adaptive.get("replans") or []
        t_s, t_a = adaptive.get("static_s"), adaptive.get("adaptive_s")
        print(f"check_regression: adaptive static={t_s:.3f}s "
              f"adaptive={t_a:.3f}s replans={len(replans)} beats_static="
              f"{adaptive.get('adaptive_beats_static')}", flush=True)
        if not replans:
            failures.append("adaptive run performed no replans on the "
                            "skewed star (estimate-vs-observed trigger "
                            "went dark)")
        if not adaptive.get("adaptive_beats_static"):
            failures.append(f"adaptive execution ({t_a:.3f}s) did not "
                            f"beat static ({t_s:.3f}s) on the skewed star")

    slo = rollup.get("benchmarks", {}).get("slo_bench")
    if slo and slo.get("ok") and slo.get("payload"):
        with open(BASELINE_PATH) as f:
            slo_base = json.load(f).get("slo_bench", {})
        sp = slo["payload"]
        hit, shed = sp["deadline_hit_rate"], sp["shed_rate"]
        floor = slo_base.get("smoke_hit_rate_floor", 0.0)
        ceil = slo_base.get("smoke_shed_rate_ceiling", 1.0)
        print(f"check_regression: smoke slo hit_rate={hit:.2f} "
              f"(floor {floor:.2f}), shed_rate={shed:.2f} "
              f"(ceiling {ceil:.2f}), structured="
              f"{sp['sheds_structured']}", flush=True)
        if hit < floor:
            failures.append(f"smoke slo deadline hit rate {hit:.2f} below "
                            f"baseline floor {floor:.2f}")
        if shed > ceil:
            failures.append(f"smoke slo shed rate {shed:.2f} above "
                            f"baseline ceiling {ceil:.2f}")
        if not sp["sheds_structured"]:
            failures.append("smoke slo shed queries missing structured "
                            "Backpressure errors")
        # -- closed-loop observability gates ----------------------------
        steady = sp.get("slo_alerts_steady")
        burst = sp.get("slo_alerts_burst")
        stale = sp.get("cost_model_staleness")
        print(f"check_regression: slo alerts steady={steady} (want 0), "
              f"burst={burst} (want >=1), flight_dump_valid="
              f"{sp.get('flight_dump_valid')}, staleness={stale}",
              flush=True)
        if steady != 0:
            failures.append(f"SLO monitor fired {steady} alert(s) at "
                            f"steady state (want 0 — alerts that fire "
                            f"when nothing is wrong are noise)")
        if not burst:
            failures.append("SLO monitor stayed silent through the "
                            "bursty overload replay (want >= 1 alert)")
        if not sp.get("flight_dump_valid"):
            failures.append("flight-recorder dump missing or "
                            "schema-invalid")
        if not isinstance(stale, (int, float)) or not math.isfinite(stale):
            failures.append(f"cost_model_staleness gauge missing or "
                            f"non-finite: {stale!r}")
        # -- resilience gates --------------------------------------------
        pre = sp.get("preemption") or {}
        if not pre:
            failures.append("preemption on-vs-off section missing from "
                            "slo payload")
        else:
            g_on = pre.get("gold_hit_rate_on")
            g_off = pre.get("gold_hit_rate_off")
            n_pre = int(pre.get("preemptions") or 0)
            print(f"check_regression: preemption gold_hit on={g_on:.2f} "
                  f"off={g_off:.2f}, preemptions={n_pre}", flush=True)
            if n_pre < 1:
                failures.append("deadline preemption never fired under "
                                "the overload replay (want >= 1)")
            if not pre.get("preempt_improves"):
                failures.append(f"preemption did not improve the gold "
                                f"deadline hit rate at equal offered "
                                f"load (on={g_on:.2f} < off={g_off:.2f})")
        chaos = sp.get("chaos") or {}
        if not chaos:
            failures.append("chaos smoke section missing from slo payload")
        else:
            print(f"check_regression: chaos completed="
                  f"{chaos.get('completed')} unstructured="
                  f"{chaos.get('unstructured_failures')} row_exact="
                  f"{chaos.get('row_exact')} hung_workers="
                  f"{chaos.get('hung_workers')} failed="
                  f"{chaos.get('failed')} breakers="
                  f"{chaos.get('breakers')}", flush=True)
            if chaos.get("unstructured_failures") != 0:
                failures.append(f"chaos replay leaked "
                                f"{chaos.get('unstructured_failures')} "
                                f"unstructured failure(s) (want 0 — every"
                                f" abort must be structured Backpressure)")
            if not chaos.get("row_exact"):
                failures.append("chaos replay results were not row-exact "
                                "against the NumPy oracle")
            if chaos.get("hung_workers") != 0:
                failures.append(f"{chaos.get('hung_workers')} worker(s) "
                                f"still alive after drain-close")
            if chaos.get("failed") != 0:
                failures.append(f"chaos replay counted "
                                f"{chaos.get('failed')} hard failure(s) "
                                f"(recovery ladder must absorb injected "
                                f"faults)")
            breakers = chaos.get("breakers") or {}
            bad = {k: b for k, b in breakers.items()
                   if b.get("state") not in ("closed", "open",
                                             "half_open")}
            if bad:
                failures.append(f"breaker(s) in unknown state: {bad}")
            opened = any(b.get("state") != "closed"
                         for b in breakers.values())
            if opened and not chaos.get("breaker_events"):
                failures.append("a breaker opened without emitting any "
                                "structured breaker event")
    else:
        print("check_regression: no successful slo_bench payload — "
              "skipping SLO gate", flush=True)

    for msg in failures:
        print(f"check_regression: FAIL — {msg}", flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
