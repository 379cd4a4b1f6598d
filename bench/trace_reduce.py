"""Reduce a profiler trace of one measured window to the benchmark's numbers.

The trace is JAX's ``.xplane.pb``.  ``load_xplane`` turns it into plain
planes -> lines -> events (name, start and duration in ns, stats), and
everything else here works on that plain form, so a test can feed a
synthetic trace.

* The window is the host span ``bench.window`` that the harness opens
  around the measured queries.
* Busy time of a chip is the union of the intervals of its ``XLA Ops``
  events inside the window; ``busy_s`` averages it over the chips used.
* Idle gaps are the stretches of the window in which a chip ran no
  operation.  Each is named by what the host was doing: the innermost
  ``bench.*`` span around the harness's own calls that covers most of it
  (else the one that overlaps it most), and the innermost other host
  event that covers most of it.
* Device ops are named by their HLO instruction and result shape (the
  trace names an op by its whole HLO line); kernel time is the summed
  duration of the ops a matcher accepts.
"""
from __future__ import annotations

import glob
import os

WINDOW_SPAN = "bench.window"
BENCH_PREFIX = "bench."
DEVICE_LINES = ("XLA Ops",)
TOP = 10


def load_xplane(path: str) -> list[dict]:
    """Planes of an ``.xplane.pb`` as plain dicts."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        stats = _is_device_plane(plane.name)
        for line in plane.lines:
            events = [(ev.name, int(ev.start_ns), int(ev.duration_ns),
                       dict(ev.stats) if stats else {})
                      for ev in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def find_xplane(log_dir: str) -> str | None:
    hits = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                         "*.xplane.pb")))
    return hits[-1] if hits else None


def _is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name


def device_planes(planes: list[dict], device_ids=None) -> list[dict]:
    """The accelerator planes, only those of ``device_ids`` when given."""
    out = [p for p in planes if _is_device_plane(p["name"])]
    if device_ids is not None:
        want = {f":{int(i)}" for i in device_ids}
        out = [p for p in out
               if any(p["name"].endswith(w) for w in want)]
    return out


def host_events(planes: list[dict]) -> list[tuple]:
    return [ev for p in planes if p["name"].startswith("/host:")
            for line in p["lines"] for ev in line["events"]]


def window_of(planes: list[dict], name: str = WINDOW_SPAN
              ) -> tuple[int, int] | None:
    spans = [(s, s + d) for (n, s, d, _) in host_events(planes) if n == name]
    if not spans:
        return None
    return min(s for s, _ in spans), max(e for _, e in spans)


def op_name(hlo: str) -> str:
    """``%fusion.3 = s32[8]{0:T(1024)} fusion(...)`` -> ``fusion.3 s32[8]``."""
    name, sep, rest = hlo.partition(" = ")
    if not sep:
        return hlo
    out, depth = [], 0
    for ch in rest:
        if ch == "{":
            depth += 1
        elif ch == "}":
            depth -= 1
        elif depth == 0:
            if ch == " " and out and out[-1] != ",":
                break
            out.append(ch)
    shape = "".join(out)
    if len(shape) > 60:
        shape = shape[:57] + "..."
    return f"{name.lstrip('%')} {shape}"


def op_events(plane: dict, lo: int, hi: int) -> list[tuple]:
    """(name, start, end) of the plane's device ops, clipped to [lo, hi]."""
    out = []
    for line in plane["lines"]:
        if line["name"] not in DEVICE_LINES:
            continue
        for (n, s, d, _) in line["events"]:
            a, b = max(s, lo), min(s + d, hi)
            if b > a:
                out.append((op_name(n), a, b))
    return out


def merge(intervals) -> list[tuple[int, int]]:
    """Union of (start, end) intervals, sorted and disjoint."""
    out: list[list[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def gaps(busy: list[tuple[int, int]], lo: int, hi: int
         ) -> list[tuple[int, int]]:
    """The stretches of [lo, hi] that ``busy`` (merged) leaves uncovered."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def _overlap(a: int, b: int, s: int, e: int) -> int:
    return max(0, min(b, e) - max(a, s))


def label_gap(a: int, b: int, host: list[tuple]) -> str:
    """What the host did during [a, b]: ``<bench span>/<host event>``."""
    bench = {"covers": (None, None), "overlaps": ("none", 0)}
    inner, inner_dur = None, None
    for (n, s, d, _) in host:
        ov = _overlap(a, b, s, s + d)
        if ov <= 0 or n == WINDOW_SPAN:
            continue
        covers = 2 * ov >= (b - a)
        if n.startswith(BENCH_PREFIX):
            if covers and (bench["covers"][1] is None
                           or d < bench["covers"][1]):
                bench["covers"] = (n, d)
            if ov > bench["overlaps"][1]:
                bench["overlaps"] = (n, ov)
        elif covers and (inner_dur is None or d < inner_dur):
            inner, inner_dur = n, d
    label = bench["covers"][0] or bench["overlaps"][0]
    return label if inner is None else f"{label}/{inner}"


def reduce_trace(planes: list[dict], device_ids=None) -> dict | None:
    """The window's device numbers, or None when the trace has no window
    or no device plane (nothing to read)."""
    win = window_of(planes)
    devs = device_planes(planes, device_ids)
    if win is None or not devs:
        return None
    lo, hi = win
    host = host_events(planes)
    busy_total = 0
    op_time: dict[str, int] = {}
    all_gaps = []
    for plane in devs:
        ops = op_events(plane, lo, hi)
        busy = merge((s, e) for _, s, e in ops)
        busy_total += sum(e - s for s, e in busy)
        for n, s, e in ops:
            op_time[n] = op_time.get(n, 0) + (e - s)
        all_gaps += gaps(busy, lo, hi)
    window_ns = hi - lo
    busy_ns = busy_total / len(devs)
    top_gaps = sorted(all_gaps, key=lambda g: g[0] - g[1])[:TOP]
    return {
        "window_s": window_ns / 1e9,
        "busy_s": busy_ns / 1e9,
        "idle_share": 1.0 - busy_ns / window_ns if window_ns else 0.0,
        "op_s": {n: t / 1e9 for n, t in op_time.items()},
        "device_ops": [[n, t / 1e9] for n, t in
                       sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[label_gap(a, b, host), (b - a) / 1e9]
                      for a, b in top_gaps],
    }


def kernel_seconds(reduced: dict | None, match) -> float | None:
    """Summed device time of the ops whose name ``match`` accepts, or
    None where no such op ran."""
    if not reduced:
        return None
    hits = [t for n, t in reduced["op_s"].items() if match(n)]
    return sum(hits) if hits else None
