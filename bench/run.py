#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip, and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is found by name under this directory:

    workloads/<cell>.json      configuration, traffic mix, chips, why
    configs/<config>.json      the deployment's sizes and guarantees
    configs/<config>.py        its plain NumPy reference
    traffic/<mix>.json         the traffic mix: a kind and its parameters
    traffic/<kind>.py          the generator and client of that kind
    metrics/<metric>.py        one per-layer metric: ``UNIT`` and ``read``

A run: find the chip (none, or fewer than the cell asks for: exit 2 with no
result), set up (data from ``--seed``, the calibrated planner, warm-up of
the cell's shapes) as ``setup_s``, run the cell's clients closed-loop for
``--seconds`` and finish the queries in flight, then compare what the
window produced with the reference.  With ``--trace 1`` the window runs
under the profiler and the result carries the per-layer metrics, the
device's busy time and a breakdown; with ``--trace 0`` the end-to-end
metrics.  The last line of standard output is the result; the numbers
compared, each beside its limit, are the last lines of standard error.
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import os
import shutil
import statistics
import sys
import threading
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
NAME_CHARS = set("abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ"
                 "0123456789_.-")


class BenchError(Exception):
    """A run that cannot be made: exit non-zero and print no result."""


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# Discovery: every part of a cell is a file named after it.
# ---------------------------------------------------------------------------

def _check_name(name: str) -> str:
    if not name or not set(name) <= NAME_CHARS or name[0] in ".-":
        raise BenchError(f"not a benchmark name: {name!r}")
    return name


def load_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise BenchError(f"missing benchmark file {path}") from None


def load_module(path: str, name: str):
    if not os.path.isfile(path):
        raise BenchError(f"missing benchmark file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def names_in(root: str, sub: str, ext: str) -> list[str]:
    d = os.path.join(root, sub)
    if not os.path.isdir(d):
        return []
    return sorted(f[:-len(ext)] for f in os.listdir(d)
                  if f.endswith(ext) and not f.startswith("_"))


def discover(root: str = BENCH) -> dict:
    """Names of every configuration, cell, traffic mix and kind, and
    per-layer metric found under ``root``."""
    return {"configs": names_in(root, "configs", ".json"),
            "workloads": names_in(root, "workloads", ".json"),
            "traffic": names_in(root, "traffic", ".json"),
            "kinds": names_in(root, "traffic", ".py"),
            "metrics": names_in(root, "metrics", ".py")}


@dataclasses.dataclass
class CellSpec:
    name: str
    workload: dict
    config: dict
    mix: dict
    kind: object          # traffic/<kind>.py
    reference: object     # configs/<config>.py


def load_cell(name: str, root: str = BENCH) -> CellSpec:
    wl = load_json(os.path.join(root, "workloads", _check_name(name) + ".json"))
    cfg_name = _check_name(wl["config"])
    cfg = load_json(os.path.join(root, "configs", cfg_name + ".json"))
    mix = load_json(os.path.join(root, "traffic",
                                 _check_name(wl["traffic"]) + ".json"))
    kind = _check_name(mix["kind"])
    return CellSpec(
        name, wl, cfg, mix,
        load_module(os.path.join(root, "traffic", kind + ".py"),
                    f"bench_traffic_{kind}"),
        load_module(os.path.join(root, "configs", cfg_name + ".py"),
                    f"bench_reference_{cfg_name}"))


def load_metrics(root: str = BENCH) -> dict:
    return {m: load_module(os.path.join(root, "metrics", m + ".py"),
                           f"bench_metric_{m}")
            for m in names_in(root, "metrics", ".py")}


# ---------------------------------------------------------------------------
# The chip, the compile cache and the calibrated planner.
# ---------------------------------------------------------------------------

def require_chips(jax, chips: int) -> list:
    """The devices the cell runs on; refuses anything but enough TPUs."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX's first device is "
                         f"{devices[0].platform!r}; this benchmark never "
                         f"runs on the CPU")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees "
                         f"{len(devices)}")
    return devices[:chips]


def cache_dir(root: str, *parts: str) -> str:
    """A fixed directory inside the checkout (part of the compile cache's
    key, so it never moves)."""
    path = os.path.join(root, "_cache", *parts)
    os.makedirs(path, exist_ok=True)
    return path


def enable_compile_cache(jax, root: str) -> str:
    path = cache_dir(root, "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def close_compile_cache(jax) -> None:
    """Neither read nor write the persistent cache from here on.  Set-up
    has loaded every program its warm-up needed; a program the window
    then needs for data it has not seen is compiled in the window in every
    run, also when an earlier run in this checkout had the same seed."""
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()


def calibrated_planner(cp, path: str):
    """``QueryPlanner.calibrated`` as deployed, measured once per cell and
    checkout: the first run measures and writes the unit costs to
    ``path``, later runs build the same planner from them."""
    from repro.core.pass_planner import PassPlanner
    from repro.engine import QueryPlanner

    if os.path.isfile(path):
        with open(path) as f:
            c = json.load(f)
        log(f"planner: calibration loaded from {os.path.basename(path)}")
    else:
        t0 = time.perf_counter()
        p = QueryPlanner.calibrated(cp)
        pp = p.pass_planner
        c = {"u_overrides": {k: list(v) for k, v in p.u_overrides.items()},
             "partition": [pp.u_n1, pp.u_n2, pp.u_n3],
             "handoff_latency_s": p.handoff_latency_s,
             "handoff_bw_bytes_per_s": p.handoff_bw_bytes_per_s}
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(c, f)
        os.replace(tmp, path)
        log(f"planner: calibrated on the chip in "
            f"{time.perf_counter() - t0:.3f} s")
    log(f"planner: partition s/item n1,n2,n3={c['partition']} handoff "
        f"latency={c['handoff_latency_s']:.3e} s "
        f"bw={c['handoff_bw_bytes_per_s']:.3e} B/s")
    return QueryPlanner(
        u_overrides={k: tuple(v) for k, v in c["u_overrides"].items()},
        pass_planner=PassPlanner(*c["partition"]), partition_device_g=None,
        handoff_latency_s=c["handoff_latency_s"],
        handoff_bw_bytes_per_s=c["handoff_bw_bytes_per_s"])


@dataclasses.dataclass
class Context:
    """What a traffic kind gets from the harness."""

    cell: str
    seed: int
    params: dict
    config: dict
    reference: object
    devices: list
    root: str

    def service(self):
        """``JoinQueryService`` as users get it: a ``CoProcessor`` over
        the cell's chips, the calibrated planner, every other setting at
        its default."""
        from repro.core import CoProcessor
        from repro.engine import JoinQueryService

        cp = CoProcessor(c_devices=self.devices, g_devices=self.devices)
        planner = calibrated_planner(
            cp, os.path.join(cache_dir(self.root, "calib"),
                             self.cell + ".json"))
        return JoinQueryService(cp=cp, planner=planner)


# ---------------------------------------------------------------------------
# The measured window: closed loop, every query in flight finished.
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Record:
    index: int
    t_submit: float
    t_done: float
    value: object = None          # what the kind's ``execute`` returned
    error: str | None = None

    @property
    def latency_s(self) -> float:
        return self.t_done - self.t_submit


def run_window(cell, seconds: float, clients: int, clock=time.perf_counter
               ) -> tuple[float, list[Record]]:
    """Each client prepares (untimed) and executes (timed, submit to
    result ready) query after query until ``seconds`` have passed since
    the start; a query started before then is finished.  Returns the
    window's start and its records in completion order."""
    import jax

    lock = threading.Lock()
    records: list[Record] = []
    started = 0
    t_start = clock()
    t_end = t_start + seconds

    def client():
        nonlocal started
        while True:
            with lock:
                # Query 0 always runs: every window completes a query.
                if started and clock() >= t_end:
                    return
                i, started = started, started + 1
            with jax.profiler.TraceAnnotation("bench.prepare"):
                item = cell.prepare(i)
            t0 = clock()
            try:
                with jax.profiler.TraceAnnotation("bench.execute"):
                    value, err = cell.execute(item), None
            except Exception as e:          # a failed query is counted
                value, err = None, f"{type(e).__name__}: {e}"
            rec = Record(i, t0, clock(), value, err)
            with lock:
                records.append(rec)

    threads = [threading.Thread(target=client, name=f"bench-client-{k}")
               for k in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return t_start, records


def window_stats(t_start: float, records: list[Record], failed) -> dict:
    """End-to-end numbers of one window.  ``failed(record)`` says whether
    a query that returned was answered off the device path (from the
    NumPy reference)."""
    bad = [r for r in records if r.error is not None or failed(r)]
    good = [r for r in records if r not in bad]
    last = max((r.t_done for r in records), default=t_start)
    elapsed = last - t_start
    return {"attempted": len(records), "failed": len(bad),
            "completed": len(good), "elapsed_s": elapsed,
            "queries_per_s": len(good) / elapsed if elapsed > 0 else 0.0,
            "latency_p50_ms": (statistics.median(r.latency_s for r in good)
                               * 1e3 if good else None)}


# ---------------------------------------------------------------------------
# Readings for the per-layer metric readers.
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Readings:
    """Everything a ``metrics/<name>.py`` reader may read about one
    window.  A reader returns None where it finds nothing to read."""

    cell: str
    completed: int
    elapsed_s: float
    layers: list            # per completed query: the kind's ``layer`` dict
    compiles: object        # CompileCounts inside the window
    plan_s: float           # the service tracer's ``plan`` spans
    ledger_bytes: int       # TransferLedger bytes, all causes
    trace: dict | None      # trace_reduce.reduce_trace of the window
    peaks: dict             # bench/peaks.json row of this device


def plan_seconds(tracer, lo: float, hi: float) -> float:
    return sum(s.t1 - s.t0 for s in tracer.spans()
               if s.name == "plan" and lo <= s.t0 <= hi)


def read_metrics(metrics: dict, r: Readings) -> dict:
    out = {}
    for name, mod in metrics.items():
        v = mod.read(r)
        if v is not None:
            out[name] = {"value": float(v), "unit": mod.UNIT}
    return out


def device_peaks(root: str, kind: str) -> dict:
    table = load_json(os.path.join(root, "peaks.json"))
    if kind not in table["devices"]:
        raise BenchError(f"device {kind!r} is not in peaks.json")
    return table["devices"][kind]


# ---------------------------------------------------------------------------
# One run.
# ---------------------------------------------------------------------------

def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def import_program(root: str) -> None:
    """Put the system under test on the path, before JAX is imported."""
    # libtpu logs to /tmp/tpu_logs unless told otherwise: a run writes
    # only inside its checkout and the directories it is given.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    src = os.path.join(os.path.dirname(root), "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise BenchError(f"the system under test is not at {src}")
    if src not in sys.path:
        sys.path.insert(0, src)


def result_line(correct, stats, metrics, device, checks, breakdown=None
                ) -> str:
    out = {"correct": bool(correct), "attempted": stats["attempted"],
           "failed": stats["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in checks}
    return json.dumps(out)


def run(argv=None, *, root: str = BENCH, require_tpu: bool = True) -> str:
    """One run; returns the result line.  ``require_tpu=False`` is for the
    harness's own tests on the CPU."""
    args = parse_args(argv)
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    spec = load_cell(args.workload, root)
    import_program(root)
    import jax

    chips = int(spec.workload["chips"])
    devices = (require_chips(jax, chips) if require_tpu
               else jax.devices()[:chips])
    dev = devices[0]
    peaks = device_peaks(root, dev.device_kind) if require_tpu else {}
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}")
    log(f"compile cache: {enable_compile_cache(jax, root)}")
    from compile_clock import CompileClock
    clock = CompileClock(jax.monitoring)

    t0 = time.perf_counter()
    ctx = Context(spec.name, args.seed, spec.mix["params"], spec.config,
                  spec.reference, devices, root)
    cell = spec.kind.Cell(ctx)
    try:
        cell.setup()
        setup_s = time.perf_counter() - t0
        c_setup = clock.snapshot()
        log(f"setup: setup_s={setup_s:.3f} {c_setup.line()}")
        close_compile_cache(jax)
        svc = cell.service
        ledger0 = svc.ledger.summary()["total_bytes"]
        stats0 = svc.stats()
        trace_dir = None
        if args.trace:
            trace_dir = cache_dir(root, "trace", spec.name)
            shutil.rmtree(trace_dir)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench.window"):
            t_start, records = run_window(cell, args.seconds, cell.clients)
        if args.trace:
            jax.profiler.stop_trace()
        c_window = clock.snapshot() - c_setup
        stats = window_stats(
            t_start, records, lambda r: cell.reference_answers(r.value) > 0)
        done = [r for r in records if r.error is None]
        log(f"window: attempted={stats['attempted']} completed="
            f"{stats['completed']} failed={stats['failed']} elapsed_s="
            f"{stats['elapsed_s']:.3f} {c_window.line()}")
        for r in records:
            if r.error is not None:
                log(f"query {r.index} failed: {r.error}")
        service_lines(svc, stats0, done, cell)
        peak = max(d.memory_stats().get("peak_bytes_in_use", 0)
                   if d.memory_stats() else 0 for d in devices)
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(devices), "memory_peak_bytes": int(peak)}
        breakdown = None
        if args.trace:
            reduced = reduce_window_trace(trace_dir, devices)
            if reduced is not None:
                device["busy_s"] = reduced["busy_s"]
                device["window_s"] = reduced["window_s"]
                breakdown = {"device_ops": reduced["device_ops"],
                             "idle_gaps": reduced["idle_gaps"]}
            readings = Readings(
                spec.name, stats["completed"], stats["elapsed_s"],
                [cell.layer(r.value) for r in done
                 if not cell.reference_answers(r.value)], c_window,
                plan_seconds(svc.tracer, t_start,
                             t_start + stats["elapsed_s"]),
                svc.ledger.summary()["total_bytes"] - ledger0, reduced,
                peaks)
            metrics = read_metrics(load_metrics(root), readings)
            reduced = None
        else:
            metrics = {"setup_s": {"value": setup_s, "unit": "s"},
                       "queries_per_s": {"value": stats["queries_per_s"],
                                         "unit": "queries/s"}}
            if stats["latency_p50_ms"] is not None:
                metrics["latency_p50_ms"] = {
                    "value": stats["latency_p50_ms"], "unit": "ms"}
        svc = None          # the program's state goes with the service
        cell.release()
        checks = cell.check(done)
    finally:
        cell.close()
    correct = (bool(checks) and stats["completed"] > 0
               and all(v <= lim for _, v, lim in checks))
    for n, v, lim in checks:
        print(f"check {n}: {v} limit {lim}", file=sys.stderr, flush=True)
    return result_line(correct, stats, metrics, device, checks, breakdown)


def service_lines(svc, stats0, done, cell) -> None:
    """The window's plans, cache hits and recovery counters."""
    st = svc.stats()
    cache0, cache = stats0["cache"] or {}, st["cache"] or {}
    hits = {k: cache.get(k, 0) - cache0.get(k, 0)
            for k in ("hits", "partition_hits", "probe_partition_hits")}
    plans: dict = {}
    for r in done:
        for p in cell.plans(r.value):
            plans[p] = plans.get(p, 0) + 1
    log("plans: " + ", ".join(f"{k} x{v}" for k, v in sorted(plans.items())))
    res0, res = stats0["resilience"], st["resilience"]
    ref = sum(cell.reference_answers(r.value) for r in done)
    log(f"build_table_cache: " + " ".join(f"{k}={v}" for k, v in hits.items())
        + f"; reference_path={ref} retries="
        f"{res['retries'] - res0['retries']} breaker_short_circuits="
        f"{res['breaker_short_circuits'] - res0['breaker_short_circuits']}")
    by_cause = (st.get("host_transfer_ledger") or {}).get("by_cause", {})
    log("transfer_ledger_bytes: " + " ".join(
        f"{k}={v}" for k, v in sorted(by_cause.items())))


def reduce_window_trace(trace_dir: str, devices) -> dict | None:
    import trace_reduce

    path = trace_reduce.find_xplane(trace_dir)
    if path is None:
        return None
    reduced = trace_reduce.reduce_trace(trace_reduce.load_xplane(path),
                                        [d.id for d in devices])
    shutil.rmtree(trace_dir, ignore_errors=True)
    return reduced


def main(argv=None) -> int:
    try:
        line = run(argv)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
