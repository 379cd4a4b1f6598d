"""Two-group co-processing executor: OL / DD / PL on real devices (§3.2).

The paper's coupled CPU+GPU is re-created as two *device groups* (DESIGN.md
§2): a small C-group and a large G-group.  On this container the groups are
host CPU devices (spawned with --xla_force_host_platform_device_count in the
benchmark harness); on a pod they are chip groups of one mesh.  "Coupled"
executions exchange intermediates directly (zero-copy / ICI); "discrete"
executions add the paper's emulated bus delay (§5.1: latency + size/bw).

Schemes:
  * CPU_ONLY / GPU_ONLY — whole series on one group.
  * OL  — per-step 0/1 assignment (paper: degenerates to GPU-only when the
          GPU wins every step — our Fig. 4 analogue decides).
  * DD  — one ratio for all steps of a phase; separate tables need a merge.
  * PL  — per-step ratios with boundary exchanges (fine-grained scheme).
  * BASIC_UNIT — appendix baseline: dynamic chunk scheduling.

Build-table modes (§3.3):
  * separate — each group builds a partial table on its tuple share; an
    explicit merge combines them (the paper's Fig. 3 merge overhead).
  * shared   — one logical table, bucket-range ownership split between the
    groups; tuples are exchanged to their owning group (the distributed
    analogue of writing one table in shared memory; no merge step).
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import os
import threading
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import hash_table as ht
from .cost_model import LinkSpec, ZEROCOPY_LINK
from .relation import Relation, bucket_of
from .shj import concat_results


def _round_up(n: int, k: int) -> int:
    return ((n + k - 1) // k) * k


# Fault-injection hook: ``repro.engine.faults.install`` plants its
# ``maybe_fault`` here (set back to None on uninstall), so the hot path
# costs one load and one branch when no injector is active, and this
# module never imports the engine package (which imports it back).
_FAULT_HOOK = None


def _maybe_fault(site: str) -> None:
    hook = _FAULT_HOOK
    if hook is not None:
        hook(site)


@dataclasses.dataclass
class Timing:
    wall_s: float = 0.0
    phase_s: dict = dataclasses.field(default_factory=dict)
    transfer_bytes: int = 0
    transfer_s: float = 0.0
    notes: dict = dataclasses.field(default_factory=dict)
    # Observability hook: phases timed through ``phase()`` also emit
    # tracer spans (nested under whatever query span the calling thread
    # has open).  ``None``/disabled tracer keeps the old perf_counter
    # behavior with no extra work.  Excluded from equality/repr — two
    # timings are the same measurement regardless of who observed them.
    tracer: object = dataclasses.field(default=None, repr=False,
                                       compare=False)

    @contextlib.contextmanager
    def phase(self, name: str, **attrs):
        """Time a phase into ``phase_s[name]`` (and span it when traced).

        Phase seconds always come from ``time.perf_counter`` — the
        tracer's (possibly fake) clock only stamps the span — so cost-
        model feedback stays on real time even under test clocks.
        """
        tracer = self.tracer
        traced = tracer is not None and getattr(tracer, "enabled", False)
        if traced:
            ctx = tracer.span(name, **attrs)
        else:
            ctx = contextlib.nullcontext()
        with ctx:
            t0 = time.perf_counter()
            try:
                yield self
            finally:
                self.phase_s[name] = time.perf_counter() - t0

    def to_dict(self) -> dict:
        """JSON-serializable view (machine-readable bench artifacts)."""
        return {
            "wall_s": float(self.wall_s),
            "phase_s": {k: float(v) for k, v in self.phase_s.items()},
            "transfer_bytes": int(self.transfer_bytes),
            "transfer_s": float(self.transfer_s),
            "notes": {k: (v if isinstance(v, (int, float, str, bool, list))
                          else str(v)) for k, v in self.notes.items()},
        }


class DeviceGroup:
    """A set of devices acting as one logical processor (C or G).

    ``owner`` (the ``CoProcessor``) lends its ``tracer`` to the group's
    launches: each call of a ``jit`` program is a ``launch`` span naming
    the program (``key[0]``) and the group, which closes with the
    compiling done inside it (``compile_s`` / ``compiles``): the
    per-program compile record.
    """

    def __init__(self, name: str, devices, owner=None):
        self.name = name
        self.owner = owner
        self.devices = list(devices)
        if len(self.devices) > 1:
            self.mesh = jax.sharding.Mesh(np.array(self.devices), ("i",))
            self.sharding = jax.sharding.NamedSharding(
                self.mesh, jax.sharding.PartitionSpec("i"))
        else:
            self.mesh = None
            self.sharding = jax.sharding.SingleDeviceSharding(self.devices[0])
        self.replicated = (jax.sharding.NamedSharding(
            self.mesh, jax.sharding.PartitionSpec())
            if self.mesh else self.sharding)
        self._jit_cache: dict = {}
        self._jit_lock = threading.Lock()

    @property
    def size(self) -> int:
        return len(self.devices)

    def put_items(self, tree):
        """Place per-item arrays on the group (leading axis sharded)."""
        _maybe_fault("h2d")
        return jax.tree.map(lambda x: jax.device_put(x, self.sharding), tree)

    def put_shared(self, tree):
        return jax.tree.map(lambda x: jax.device_put(x, self.replicated), tree)

    def pad_to(self, n: int) -> int:
        return _round_up(max(n, self.size), self.size)

    def jit(self, key, fn):
        # Lock: the engine's worker threads share one CoProcessor, so the
        # compile cache sees concurrent lookups for the same key.
        with self._jit_lock:
            cached = self._jit_cache.get(key)
            if cached is None:
                jf = jax.jit(fn)

                def cached(*args, _jf=jf, _program=str(key[0]), **kw):
                    _maybe_fault("kernel")   # launch-site fault injection
                    return self._launch(_program, _jf, args, kw)

                self._jit_cache[key] = cached
            return cached

    def _launch(self, program: str, jf, args, kw):
        from repro.obs import NULL_TRACER
        tracer = getattr(self.owner, "tracer", NULL_TRACER)
        if not tracer.enabled:
            return jf(*args, **kw)
        with tracer.span("launch", program=program, group=self.name):
            return jf(*args, **kw)


class CoProcessor:
    """Executes hash-join step series across a C-group and a G-group.

    PHJ orchestration and the BasicUnit baseline are attached from
    ``PhjCoProcessorMixin`` at the bottom of this module."""

    def __init__(self, c_devices=None, g_devices=None, *,
                 link: LinkSpec = ZEROCOPY_LINK, discrete: bool = False,
                 ratio_quantum: int = 64, tracer=None):
        # Observability: phase timings flow through ``Timing.phase`` and
        # emit spans on this tracer; host crossings go through
        # ``self.boundary`` into ``ledger`` (cause ``exchange``) and
        # ``metrics`` (``host_syncs``).  The defaults are the
        # shared no-op recorder and no ledger or registry, so a standalone
        # CoProcessor records nothing and pays one branch per span;
        # ``JoinQueryService`` adopts all three.
        from repro.obs import NULL_TRACER
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.ledger = None
        self.metrics = None
        devs = jax.devices()
        if c_devices is None or g_devices is None:
            want_c = os.environ.get("REPRO_C_DEVICES")
            if want_c is not None and len(devs) >= 2:
                k = min(max(int(want_c), 1), len(devs) - 1)
                c_devices, g_devices = devs[:k], devs[k:]
            elif len(devs) >= 8:
                c_devices, g_devices = devs[:2], devs[2:]
            elif len(devs) >= 2:
                c_devices, g_devices = devs[:1], devs[1:]
            else:  # single device: both groups share it (functional mode)
                c_devices = g_devices = devs[:1]
        self.c = DeviceGroup("C", c_devices, owner=self)
        self.g = DeviceGroup("G", g_devices, owner=self)
        # Per-group execution locks for concurrent callers (the engine's
        # worker threads).  Two sharded programs with collectives must
        # never interleave on the same device group — XLA's rendezvous
        # deadlocks — but a C-only and a G-only query may overlap freely.
        # Acquire in fixed C-then-G order.
        self.group_locks = {"C": threading.Lock(), "G": threading.Lock()}
        self.link = link
        self.discrete = discrete
        self.ratio_quantum = ratio_quantum
        # Cuts and relation sizes are kept multiples of this, so both
        # groups' slices shard evenly over their devices.
        self.lcm = math.lcm(self.c.size, self.g.size)

    @property
    def boundary(self):
        """Host crossings, recorded on this CoProcessor's tracer, ledger
        and registry (cause ``exchange``)."""
        from repro.obs import HostBoundary
        return HostBoundary(self.tracer, self.ledger, self.metrics)

    def _pull(self, x, site: str, column: str = "-"):
        return self.boundary.pull(x, site=site, cause="exchange",
                                  column=column)

    def _push(self, x, place, site: str, column: str = "-"):
        return self.boundary.push(x, place, site=site, cause="exchange",
                                  column=column)

    BUILD_PAD_KEY = -2   # sentinel keys: pads never match real (>=0) keys
    PROBE_PAD_KEY = -3

    def pad_relation(self, rel: Relation, sentinel: int) -> Relation:
        n = rel.size
        m = _round_up(n, self.lcm)
        if m == n:
            return rel
        pad = m - n
        return Relation(
            jnp.concatenate([rel.rid, jnp.full((pad,), ht.INVALID)]),
            jnp.concatenate([rel.key,
                             jnp.full((pad,), jnp.int32(sentinel))]))

    # ------------------------------------------------------------------
    # Emulated bus (paper §5.1: delay = latency + size/bandwidth).
    # ------------------------------------------------------------------
    def _bus_delay(self, nbytes: int, timing: Timing):
        timing.transfer_bytes += int(nbytes)
        if self.discrete and nbytes > 0:
            d = float(self.link.xfer_time(nbytes))
            timing.transfer_s += d
            time.sleep(d)

    def _cut(self, n: int, ratio: float) -> int:
        """Quantized split point (bounds recompilation count and keeps both
        slices divisible by the group sizes).

        Exact at the endpoints: ratio 0/1 must assign the WHOLE relation to
        one group — quantization leaving a remainder slice on the other
        group would dispatch work there that callers (and the engine's
        group locks) believe cannot happen."""
        if ratio <= 0.0:
            return 0
        if ratio >= 1.0:
            return n
        q = max(self.lcm, _round_up(n // self.ratio_quantum, self.lcm))
        cut = int(round(ratio * n / q)) * q
        return min(n, max(0, cut))

    # ------------------------------------------------------------------
    # Map-series execution with per-step ratios (PL backbone).
    # ------------------------------------------------------------------
    def run_map_series(self, series, shared, items, ratios,
                       timing: Timing | None = None):
        """Run splittable map steps with per-step ratios.

        Boundary rule (paper Fig. 2): when r_i != r_{i-1}, the slice between
        the two cut points moves across groups — a real device transfer plus
        the emulated bus delay in discrete mode.
        """
        timing = timing or Timing()
        n = next(iter(items.values())).shape[0]
        shared_c = self.c.put_shared(shared)
        shared_g = self.g.put_shared(shared)
        cut = self._cut(n, ratios[0])
        items_c = self.c.put_items({k: v[:cut] for k, v in items.items()})
        items_g = self.g.put_items({k: v[cut:] for k, v in items.items()})
        if self.discrete:
            moved = sum(int(np.prod(v.shape[1:]) or 1) * v.dtype.itemsize
                        * (n - cut) for v in items.values())
            self._bus_delay(moved, timing)
        extra_shared: dict = {}
        for i, step in enumerate(series.steps):
            new_cut = self._cut(n, ratios[i])
            if new_cut != cut:
                items_c, items_g, moved = self._move_boundary(
                    items_c, items_g, cut, new_cut)
                self._bus_delay(moved, timing)
                cut = new_cut
            fc = self.c.jit((series.name, step.name, "c",
                             tuple(v.shape for v in items_c.values())),
                            step.apply)
            fg = self.g.jit((series.name, step.name, "g",
                             tuple(v.shape for v in items_g.values())),
                            step.apply)
            out_c, sh_c = fc(shared_c, items_c)   # async dispatch: C ...
            out_g, sh_g = fg(shared_g, items_g)   # ... overlaps with G
            items_c, items_g = out_c, out_g
            for k, how in step.combine.items():
                a, b = sh_c.get(k), sh_g.get(k)
                if how == "add":
                    extra_shared[k] = jax.device_put(a, self.c.replicated) + \
                        jax.device_put(jax.device_get(b), self.c.replicated)
                elif how == "list":
                    extra_shared.setdefault(k, []).extend(
                        [x for x in (a if isinstance(a, list) else [a])] +
                        [x for x in (b if isinstance(b, list) else [b])])
        return items_c, items_g, extra_shared, timing

    def _move_boundary(self, items_c, items_g, cut, new_cut):
        """Move the [min(cut,new_cut), max) slice between the groups."""
        moved_bytes = 0
        if new_cut > cut:            # C takes more: head of G moves to C
            take = new_cut - cut
            head = {k: jax.device_get(v[:take]) for k, v in items_g.items()}
            moved_bytes = sum(v.nbytes for v in head.values())
            items_c = self.c.put_items(
                {k: jnp.concatenate([jax.device_get(items_c[k]), head[k]])
                 for k in items_c})
            items_g = self.g.put_items(
                {k: jax.device_get(v[take:]) for k, v in items_g.items()})
        else:                        # G takes more: tail of C moves to G
            take = cut - new_cut
            tail = {k: jax.device_get(v[v.shape[0] - take:])
                    for k, v in items_c.items()}
            moved_bytes = sum(v.nbytes for v in tail.values())
            items_g = self.g.put_items(
                {k: jnp.concatenate([tail[k], jax.device_get(items_g[k])])
                 for k in items_g})
            items_c = self.c.put_items(
                {k: jax.device_get(v[: v.shape[0] - take])
                 for k, v in items_c.items()})
        return items_c, items_g, moved_bytes

    # ------------------------------------------------------------------
    # SHJ under a scheme.
    # ------------------------------------------------------------------
    def shj(self, build_rel: Relation, probe_rel: Relation, *,
            num_buckets: int, max_out: int,
            build_ratios, probe_ratios, table_mode: str = "shared",
            measure: bool = True) -> tuple[ht.JoinResult, Timing]:
        """Run SHJ with per-step ratios (len-4 each; DD = equal entries,
        OL = 0/1 entries, CPU-only = all 1, GPU-only = all 0)."""
        table, timing = self.build_table(build_rel, num_buckets=num_buckets,
                                         ratios=build_ratios,
                                         table_mode=table_mode)
        result, timing = self.probe_table(probe_rel, table, max_out=max_out,
                                          ratios=probe_ratios, timing=timing)
        timing.wall_s = timing.phase_s["build"] + timing.phase_s["probe"]
        return result, timing

    def build_table(self, build_rel: Relation, *, num_buckets: int, ratios,
                    table_mode: str = "shared",
                    timing: Timing | None = None
                    ) -> tuple[ht.HashTable, Timing]:
        """Build phase only, returning the finished table.

        The engine's build-table cache keeps this output resident so later
        probes against the same build relation skip the phase entirely (the
        paper's cache-reuse insight lifted to the query level)."""
        timing = timing or Timing(tracer=self.tracer)
        build_rel = self.pad_relation(build_rel, self.BUILD_PAD_KEY)
        with timing.phase("build", n=build_rel.size):
            table = self._build(build_rel, num_buckets, ratios, table_mode,
                                timing)
        return table, timing

    def probe_table(self, probe_rel: Relation, table: ht.HashTable, *,
                    max_out: int, ratios,
                    timing: Timing | None = None,
                    probe_fn=None, tag: str = "probe"
                    ) -> tuple[ht.JoinResult, Timing]:
        """Probe phase against an existing (possibly cached) table.

        ``probe_fn(max_out)`` overrides the per-group probe kernel (the
        join-variant emissions in ``repro.ops.join_variants`` route
        through here); ``tag`` keys the jit cache per kernel family.
        """
        timing = timing or Timing(tracer=self.tracer)
        probe_rel = self.pad_relation(probe_rel, self.PROBE_PAD_KEY)
        with timing.phase("probe", n=probe_rel.size):
            result = self._probe(probe_rel, table, max_out, ratios, timing,
                                 probe_fn=probe_fn, tag=tag)
            jax.block_until_ready(result.probe_rid)
        if not timing.wall_s:
            timing.wall_s = timing.phase_s.get("build", 0.0) + \
                timing.phase_s["probe"]
        return result, timing

    def _build(self, rel: Relation, num_buckets: int, ratios, table_mode,
               timing: Timing) -> ht.HashTable:
        n = rel.size
        r1 = ratios[0]
        cut = self._cut(n, r1)
        if table_mode == "separate" and 0 < cut < n:
            # Each group builds a partial table on its share; merge after.
            rel_c = self.c.put_items(rel.take(0, cut))
            rel_g = self.g.put_items(rel.take(cut, n))
            if self.discrete:
                self._bus_delay((n - cut) * 8, timing)
            fb_c = self.c.jit(("build", cut, num_buckets, "c"),
                              partial(ht.build_hash_table,
                                      num_buckets=num_buckets))
            fb_g = self.g.jit(("build", n - cut, num_buckets, "g"),
                              partial(ht.build_hash_table,
                                      num_buckets=num_buckets))
            part_c = fb_c(rel_c)
            part_g = fb_g(rel_g)
            jax.block_until_ready((part_c.rids, part_g.rids))
            with self.tracer.span("merge"):
                if self.discrete:  # ship the partial table back over the bus
                    self._bus_delay(sum(x.nbytes for x in
                                        jax.tree.leaves(part_g)), timing)
                part_g_host = self._pull(part_g, "shj.merge", "table")
                fm = self.c.jit(("merge", n, num_buckets),
                                partial(ht.merge_hash_tables,
                                        num_buckets=num_buckets))
                table = fm([part_c, self._push(part_g_host,
                                               self.c.put_shared,
                                               "shj.merge", "table")])
                jax.block_until_ready(table.rids)
            return table
        # Shared table (or degenerate single-group): bucket-range ownership.
        # C owns buckets [0, r1*B); each group receives its owned tuples and
        # builds its range; ranges concatenate into ONE table (no merge).
        own_c = self._cut(num_buckets, r1) if 0 < cut < n else \
            (num_buckets if cut == n else 0)
        if own_c in (0, num_buckets):
            grp = self.c if own_c == num_buckets else self.g
            if self.discrete and grp is self.g:
                self._bus_delay(n * 8, timing)
            fb = grp.jit(("build", n, num_buckets, grp.name),
                         partial(ht.build_hash_table, num_buckets=num_buckets))
            table = fb(grp.put_items(rel))
            jax.block_until_ready(table.rids)
            return table
        with self.tracer.span("exchange"):
            bkt = bucket_of(rel.key, num_buckets)
            to_c = bkt < own_c
            order = jnp.argsort(~to_c, stable=True)  # owners contiguous
            n_c = int(self.boundary.pull(to_c.sum(), site="shj.build.owners",
                                         cause=None))
            srel = Relation(rel.rid[order], rel.key[order])
            # Exchange: tuples cross groups to reach their owner (bounded
            # above by the full relation; discrete pays the bus for the
            # crossing part).
            crossing = min(n_c, n - cut) + min(n - n_c, cut)
            self._bus_delay(crossing * 8, timing)
            n_c_pad = _round_up(max(n_c, 1), self.lcm)
            n_g_pad = _round_up(max(n - n_c, 1), self.lcm)
            rel_c = self.c.put_items(_pad_slice(srel, 0, n_c, n_c_pad,
                                                self.BUILD_PAD_KEY))
            rel_g = self.g.put_items(_pad_slice(srel, n_c, n, n_g_pad,
                                                self.BUILD_PAD_KEY))
        fb_c = self.c.jit(("buildr", n_c_pad, num_buckets, "c"),
                          partial(ht.build_hash_table, num_buckets=num_buckets))
        fb_g = self.g.jit(("buildr", n_g_pad, num_buckets, "g"),
                          partial(ht.build_hash_table, num_buckets=num_buckets))
        part_c = fb_c(rel_c)
        part_g = fb_g(rel_g)
        with self.tracer.span("build.collect"):
            table = self._push(
                self._pull(part_g, "shj.build.collect", "table"),
                lambda t: _concat_bucket_ranges(part_c, t, own_c),
                "shj.build.collect", "table")
            jax.block_until_ready(table.rids)
        return table

    def _probe(self, rel: Relation, table: ht.HashTable, max_out: int,
               ratios, timing: Timing, *, probe_fn=None,
               tag: str = "probe") -> ht.JoinResult:
        n = rel.size
        cut = self._cut(n, ratios[0])
        # Replicate the table to both groups (coupled: zero-copy; discrete:
        # the GPU-side copy pays the bus once).
        table_bytes = sum(x.nbytes for x in jax.tree.leaves(table))
        if self.discrete and cut < n:
            self._bus_delay(table_bytes + (n - cut) * 8, timing)
        tbl_c = self.c.put_shared(table)
        tbl_g = self.g.put_shared(table)
        # Per-group result capacity: proportional to the tuple share, plus
        # slack covering statistical fluctuation of the match density (a
        # proportional cap with O(1) slack truncates skewed probes).
        slack = max(64, max_out // 16)
        max_c = max(1, _round_up(int(max_out * (cut / max(n, 1))), 8) + slack)
        max_g = max(1, max_out - max_c + 2 * slack)

        if probe_fn is None:
            def probe_fn(mo):
                return lambda r, t: ht.probe_hash_table(r, t, mo)

        res = []
        if cut > 0:
            fp = self.c.jit((tag, cut, max_c, "c"), probe_fn(max_c))
            res.append(fp(self.c.put_items(rel.take(0, cut)), tbl_c))
        if cut < n:
            fp = self.g.jit((tag, n - cut, max_g, "g"), probe_fn(max_g))
            res.append(fp(self.g.put_items(rel.take(cut, n)), tbl_g))
        if len(res) == 1:
            out = res[0]
            if self.discrete:
                count = self.boundary.pull(out.count, site="shj.probe.count",
                                           cause=None)
                self._bus_delay(int(count) * 8, timing)
            if out.probe_rid.shape[0] > max_out:
                # The per-group slack padded capacity past the caller's
                # max_out; restore the contract (valid pairs are front-
                # compacted, so a prefix slice keeps the first matches).
                out = ht.JoinResult(out.probe_rid[:max_out],
                                    out.build_rid[:max_out],
                                    jnp.minimum(out.count, max_out))
            return out
        with self.tracer.span("probe.collect"):
            res_host = [self._pull(r, "shj.probe.collect", "result")
                        for r in res]
            if self.discrete:
                self._bus_delay(int(res_host[1].count) * 8, timing)
            fcat = self.c.jit(("concat", tag,
                               tuple(r.probe_rid.shape[0] for r in res_host),
                               max_out),
                              partial(concat_results, max_out=max_out))
            return fcat([self._push(r, self.c.put_shared,
                                    "shj.probe.collect", "result")
                         for r in res_host])


def _phj_owned_join(rel_r: Relation, rel_s: Relation, *, total_bits: int,
                    shj_bits: int, max_out: int) -> ht.JoinResult:
    """Fused per-partition SHJ over a subset of partitions (see phj.py)."""
    from .relation import radix_of

    num_buckets = 1 << (total_bits + shj_bits)

    def bucket_fn(key):
        part = radix_of(key, shift=0, bits=total_bits).astype(jnp.uint32)
        sub = (jnp.uint32(0) if shj_bits == 0 else
               radix_of(key, shift=total_bits, bits=shj_bits).astype(jnp.uint32))
        return ((part << jnp.uint32(shj_bits)) | sub).astype(jnp.int32)

    bkt = bucket_fn(rel_r.key)
    order = ht.build_b2_order(bkt, rel_r.key)
    sbkt, skey = bkt[order], rel_r.key[order]
    (ukeys, krs, krc, bks, bkc, num_keys) = ht.build_b3_keylists(
        sbkt, skey, num_buckets)
    table = ht.HashTable(bks, bkc, ukeys, krs, krc, rel_r.rid[order], skey,
                         num_keys.astype(jnp.int32))
    pbkt = bucket_fn(rel_s.key)
    kstart, kcount = ht.probe_p2(table, pbkt)
    entry, nmatch = ht.probe_p3(table, rel_s.key, kstart, kcount)
    return ht.probe_p4(table, rel_s.rid, entry, nmatch, max_out)


class PhjCoProcessorMixin:
    """PHJ orchestration + the appendix's BasicUnit scheduler."""

    def _partition_side_cooperative(self, tag: str, rel: Relation,
                                    sched: tuple[int, ...],
                                    partition_ratio: float, ctx,
                                    start_pass: int, timing: "Timing",
                                    interpret: bool = False) -> Relation:
        """Ratio-split partitioning, one jitted program per pass.

        The preemptible sibling of the fused whole-schedule path: control
        returns to Python between passes so ``ctx.check`` can abort (a
        blown deadline / exhausted budget) at a pass boundary.  On abort
        the current per-group slices are collected into a partial layout
        via ``ctx.note_partial`` — the engine checkpoints it under a
        schedule-prefix cache key, and a re-admitted query resumes here
        with ``start_pass`` = completed passes.  Each pass is a stable
        reorder over its own bit slice, so the per-slice result is
        identical to the fused path's.
        """
        from .partition import partition_pass

        n = rel.size
        cut = self._cut(n, partition_ratio)
        if self.discrete and 0 < cut < n:
            self._bus_delay((n - cut) * 8, timing)
        slices = []
        if cut > 0:
            slices.append((self.c, self.c.put_items(rel.take(0, cut))))
        if cut < n:
            slices.append((self.g, self.g.put_items(rel.take(cut, n))))
        shift = sum(sched[:start_pass])

        def collect() -> Relation:
            return _collect_pieces(self, [r for _, r in slices], tag)

        for i in range(start_pass, len(sched)):
            if ctx is not None:
                try:
                    ctx.check(f"partition:{tag}:pass{i}")
                except Exception:
                    if i > 0:
                        ctx.note_partial(tag, collect(), i)
                    raise
            bits = sched[i]
            done = []
            for grp, r in slices:
                with self.tracer.span("partition.side", side=tag,
                                      group=grp.name, n=r.size,
                                      pass_index=i):
                    f = grp.jit(
                        ("part_pass", tag, r.size, shift, bits, interpret),
                        partial(partition_pass, shift=shift, bits=bits,
                                interpret=interpret, mesh=grp.mesh))
                    done.append((grp, f(r)))
            slices = done
            shift += bits
        _maybe_fault("d2h")
        return collect()

    def phj(self, build_rel: Relation, probe_rel: Relation, *,
            bits_per_pass: int | None = None, num_passes: int | None = None,
            schedule: tuple[int, ...] | None = None, planner=None,
            shj_bits: int, max_out: int,
            partition_ratio: float, join_ratio: float,
            build_parts: Relation | None = None,
            probe_parts: Relation | None = None,
            parts_out: dict | None = None, ctx=None,
            build_resume: int | None = None,
            probe_resume: int | None = None
            ) -> tuple[ht.JoinResult, "Timing"]:
        """PHJ co-processing: ratio-split partitioning, then partition-pair
        ownership split for the join phase (paper PHJ-DD/PL skeleton).

        Pass knobs may be explicit or planner-chosen (``resolve_schedule``);
        every pass runs the fused n1+n2 / stable-sort n3 data path.

        ``partition_ratio`` — C-group share of the partition passes.
        ``join_ratio``      — fraction of partition pairs owned by C.
        ``build_parts``     — an already-partitioned build relation (as a
                              prior call returned through ``parts_out``
                              under the SAME schedule): R skips the n1–n3
                              partition passes entirely.  This is what the
                              engine's partition-layout cache feeds back.
        ``probe_parts``     — same for the probe side: a replayed pipeline
                              re-probes with an identical relation, and its
                              partition passes are the larger half of the
                              cost at star-query shapes.
        ``parts_out``       — when a dict is passed, its ``"R"`` / ``"S"``
                              slots receive the freshly partitioned layouts
                              for the caller to cache (only the sides that
                              were actually partitioned this call).
        ``ctx``             — cooperative ``QueryContext``: when given,
                              partitioning runs pass-at-a-time with
                              ``ctx.check`` at every pass boundary (and
                              once before the join phase), so deadline /
                              budget preemption can abort between passes
                              and checkpoint the partial layout.
        ``build_resume`` / ``probe_resume`` — with a value ``k``, the
                              corresponding ``*_parts`` relation is a
                              *partial* layout holding the schedule's
                              first ``k`` passes (a checkpoint); the
                              remaining passes run from there.
        """
        from .partition import radix_partition_scheduled
        from .phj import resolve_schedule
        from .relation import radix_of

        timing = Timing(tracer=self.tracer)
        sched = resolve_schedule(build_rel.size, bits_per_pass=bits_per_pass,
                                 num_passes=num_passes, schedule=schedule,
                                 planner=planner)
        total_bits = sum(sched)
        timing.notes["schedule"] = list(sched)
        build_rel = self.pad_relation(build_rel, self.BUILD_PAD_KEY)
        probe_rel = self.pad_relation(probe_rel, self.PROBE_PAD_KEY)

        def part_fn(rel, mesh):
            return radix_partition_scheduled(rel, schedule=sched,
                                             mesh=mesh).rel

        with timing.phase("partition", passes=len(sched)):
            parts = {}
            if build_parts is not None and build_resume is None:
                parts["R"] = build_parts
                timing.notes["build_parts_reused"] = True
            if probe_parts is not None and probe_resume is None:
                parts["S"] = probe_parts
                timing.notes["probe_parts_reused"] = True
            todo = []
            for tag, rel, given, resume in (
                    ("R", build_rel, build_parts, build_resume),
                    ("S", probe_rel, probe_parts, probe_resume)):
                if tag in parts:
                    continue
                start = 0
                if given is not None and resume:
                    # A checkpointed partial layout: first ``resume``
                    # passes are already absorbed (stable reorders — no
                    # re-running).  Checkpoints were captured post-pad.
                    rel, start = given, int(resume)
                    timing.notes[f"{tag}_resumed_at"] = start
                todo.append((tag, rel, start))
            for tag, rel, start in todo:
                if ctx is not None or start:
                    parts[tag] = self._partition_side_cooperative(
                        tag, rel, sched, partition_ratio, ctx, start,
                        timing)
                    continue
                n = rel.size
                cut = self._cut(n, partition_ratio)
                if self.discrete and 0 < cut < n:
                    self._bus_delay((n - cut) * 8, timing)
                pieces = []
                for grp, lo, hi in ((self.c, 0, cut), (self.g, cut, n)):
                    if hi <= lo:
                        continue
                    with self.tracer.span("partition.side", side=tag,
                                          group=grp.name, n=hi - lo):
                        f = grp.jit(("phj_part", tag, hi - lo, sched),
                                    partial(part_fn, mesh=grp.mesh))
                        pieces.append(f(grp.put_items(rel.take(lo, hi))))
                _maybe_fault("d2h")
                parts[tag] = _collect_pieces(self, pieces, tag)
            if parts_out is not None:
                for tag, _, _ in todo:
                    parts_out[tag] = parts[tag]

        if ctx is not None:
            ctx.check("join")
        with timing.phase("join"):
            # Ownership exchange: partitions [0, own) -> C, rest -> G.
            num_parts = 1 << total_bits
            own = self._cut(num_parts, join_ratio)
            results = []
            # Each side's columns cross to the host once, on the first
            # group's exchange; the other group slices the same host copy.
            host_cols = {}
            for grp, sel in ((self.c, lambda pid: pid < own),
                             (self.g, lambda pid: pid >= own)):
                if (own == 0 and grp is self.c) or (own == num_parts
                                                    and grp is self.g):
                    continue
                sub = {}
                with self.tracer.span("exchange", group=grp.name):
                    for tag in ("R", "S"):
                        rel = parts[tag]
                        pid = radix_of(rel.key, shift=0, bits=total_bits)
                        mask = self._pull(sel(pid), "phj.exchange.mask",
                                          f"{tag}.pid")
                        idx = np.nonzero(mask)[0]
                        m = _round_up(max(len(idx), 1), self.lcm)
                        sent = (self.BUILD_PAD_KEY if tag == "R"
                                else self.PROBE_PAD_KEY)
                        if tag not in host_cols:
                            host_cols[tag] = self._pull(
                                rel, "phj.exchange.rows", f"{tag}.rid+key")
                        cols = host_cols[tag]
                        rid = np.full(m, -1, np.int32)
                        key = np.full(m, sent, np.int32)
                        rid[:len(idx)] = cols.rid[idx]
                        key[:len(idx)] = cols.key[idx]
                        if self.discrete:
                            self._bus_delay(len(idx) * 8 // 2, timing)
                        sub[tag] = self._push(
                            Relation(rid, key),
                            lambda r, grp=grp: grp.put_items(Relation(
                                jnp.asarray(r.rid), jnp.asarray(r.key))),
                            "phj.exchange.share", f"{tag}.rid+key")
                # Full capacity per group: partition ownership is by radix
                # value, so a skewed relation's hot partition (and all its
                # matches) can land wholly on either side regardless of
                # join_ratio — proportional caps would truncate it.
                mo = _round_up(max_out, 8) + 64
                f = grp.jit(("phj_join", sub["R"].size, sub["S"].size, mo),
                            partial(_phj_owned_join, total_bits=total_bits,
                                    shj_bits=shj_bits, max_out=mo))
                results.append(f(sub["R"], sub["S"]))
            _maybe_fault("d2h")
            with self.tracer.span("join.collect"):
                results = [self._pull(r, "phj.join.collect", "result")
                           for r in results]
                if len(results) == 1:
                    out = results[0]
                else:
                    fcat = self.c.jit(
                        ("concat",
                         tuple(r.probe_rid.shape[0] for r in results),
                         max_out), partial(concat_results, max_out=max_out))
                    out = fcat([self._push(r, self.c.put_shared,
                                           "phj.join.collect", "result")
                                for r in results])
                jax.block_until_ready(out.probe_rid)
        timing.wall_s = timing.phase_s["partition"] + timing.phase_s["join"]
        return out, timing

    # ------------------------------------------------------------------
    # Appendix A: BasicUnit — coarse-grained dynamic chunk scheduling.
    # ------------------------------------------------------------------
    def basic_unit_shj(self, build_rel: Relation, probe_rel: Relation, *,
                       num_buckets: int, max_out: int, chunk: int = 4096
                       ) -> tuple[ht.JoinResult, "Timing", dict]:
        """Chunks of tuples dynamically assigned to whichever group is free.

        Greedy least-loaded assignment using one calibrated chunk time per
        group (the appendix's dynamic queue), then real execution of the
        assigned work.  Returns the realized per-phase CPU ratios (appendix
        Figs. 17/18)."""
        timing = Timing()
        build_rel = self.pad_relation(build_rel, self.BUILD_PAD_KEY)
        probe_rel = self.pad_relation(probe_rel, self.PROBE_PAD_KEY)
        chunk = _round_up(chunk, self.lcm)
        ratios = {}
        t0 = time.perf_counter()

        def assign(n_items, t_c, t_g):
            n_chunks = -(-n_items // chunk)
            load_c = load_g = 0.0
            sched = []
            for _ in range(n_chunks):  # the paper's dynamic queue, greedily
                if load_c + t_c <= load_g + t_g:
                    sched.append("C")
                    load_c += t_c
                else:
                    sched.append("G")
                    load_g += t_g
            return sched

        # calibrate one chunk per group (build)
        cal = build_rel.take(0, chunk)
        fb = {g.name: g.jit(("bu_build", chunk, num_buckets, g.name),
                            partial(ht.build_hash_table,
                                    num_buckets=num_buckets))
              for g in (self.c, self.g)}
        tc = _time_once(fb["C"], self.c.put_items(cal))
        tg = _time_once(fb["G"], self.g.put_items(cal))
        sched = assign(build_rel.size, tc, tg)
        ratios["build"] = sched.count("C") / max(len(sched), 1)
        partials = []
        for i, who in enumerate(sched):
            grp = self.c if who == "C" else self.g
            lo = i * chunk
            hi = min(build_rel.size, lo + chunk)
            sl = _pad_slice(build_rel, lo, hi, chunk, self.BUILD_PAD_KEY)
            partials.append(fb[who](grp.put_items(sl)))
        partials = [jax.tree.map(jax.device_get, t) for t in partials]
        fm = self.c.jit(("bu_merge", len(partials), chunk, num_buckets),
                        partial(ht.merge_hash_tables, num_buckets=num_buckets))
        table = fm([self.c.put_shared(t) for t in partials])
        jax.block_until_ready(table.rids)
        t1 = time.perf_counter()
        timing.phase_s["build"] = t1 - t0

        # probe chunks
        mo = max(64, _round_up(max_out // max(1, probe_rel.size // chunk), 8)
                 + 64)
        fp = {g.name: g.jit(("bu_probe", chunk, mo, g.name),
                            lambda r, t: ht.probe_hash_table(r, t, mo))
              for g in (self.c, self.g)}
        tbl = {g.name: g.put_shared(table) for g in (self.c, self.g)}
        calp = probe_rel.take(0, chunk)
        tcp = _time_once(lambda r: fp["C"](r, tbl["C"]), self.c.put_items(calp))
        tgp = _time_once(lambda r: fp["G"](r, tbl["G"]), self.g.put_items(calp))
        schedp = assign(probe_rel.size, tcp, tgp)
        ratios["probe"] = schedp.count("C") / max(len(schedp), 1)
        outs = []
        for i, who in enumerate(schedp):
            grp = self.c if who == "C" else self.g
            lo = i * chunk
            hi = min(probe_rel.size, lo + chunk)
            sl = _pad_slice(probe_rel, lo, hi, chunk, self.PROBE_PAD_KEY)
            outs.append(fp[who](grp.put_items(sl), tbl[who]))
        outs = [jax.tree.map(jax.device_get, r) for r in outs]
        fcat = self.c.jit(("bu_concat", len(outs), mo, max_out),
                          partial(concat_results, max_out=max_out))
        out = fcat([self.c.put_shared(r) for r in outs])
        jax.block_until_ready(out.probe_rid)
        t2 = time.perf_counter()
        timing.phase_s["probe"] = t2 - t1
        timing.wall_s = t2 - t0
        return out, timing, ratios


def _time_once(fn, *args) -> float:
    jax.block_until_ready(fn(*args))  # compile
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    return time.perf_counter() - t0


def _collect_pieces(cp: CoProcessor, pieces, tag: str) -> Relation:
    """Per-group partition pieces pulled to the host and concatenated back
    on the default device (one relation for the join phase)."""
    host = [cp._pull(x, "phj.partition", f"{tag}.rid+key") for x in pieces]
    return cp._push(
        host, lambda h: Relation(jnp.concatenate([x.rid for x in h]),
                                 jnp.concatenate([x.key for x in h])),
        "phj.partition", f"{tag}.rid+key")


def _pad_slice(rel: Relation, lo: int, hi: int, target: int,
               sentinel: int) -> Relation:
    """rel[lo:hi] padded with sentinel tuples up to ``target`` rows."""
    rid, key = rel.rid[lo:hi], rel.key[lo:hi]
    pad = target - (hi - lo)
    if pad <= 0:
        return Relation(rid, key)
    return Relation(
        jnp.concatenate([rid, jnp.full((pad,), ht.INVALID)]),
        jnp.concatenate([key, jnp.full((pad,), jnp.int32(sentinel))]))


def _concat_bucket_ranges(part_c: ht.HashTable, part_g: ht.HashTable,
                          own_c: int) -> ht.HashTable:
    """Stitch two bucket-range tables into one logical shared table.

    C's table covers buckets [0, own_c) of the global space, G's covers
    [own_c, B).  Entry/rid indices of the G range shift by C's counts.
    """
    nk_c = part_c.ukeys.shape[0]
    nr_c = part_c.rids.shape[0]
    bkc = jnp.concatenate([part_c.bucket_key_count[:own_c],
                           part_g.bucket_key_count[own_c:]])
    ukeys = jnp.concatenate([part_c.ukeys, part_g.ukeys])
    krs = jnp.concatenate([part_c.key_rid_start,
                           part_g.key_rid_start + nr_c])
    krc = jnp.concatenate([part_c.key_rid_count, part_g.key_rid_count])
    rids = jnp.concatenate([part_c.rids, part_g.rids])
    skeys = jnp.concatenate([part_c.skeys, part_g.skeys])
    num_keys = part_c.num_keys + part_g.num_keys
    # Re-point G's bucket starts past C's padded tail: C's valid entries are
    # [0, nk_valid_c); G's live at [nk_c, nk_c + ...).  Adjust offset.
    bks = jnp.concatenate([
        part_c.bucket_key_start[:own_c],
        part_g.bucket_key_start[own_c:] + nk_c,
    ])
    return ht.HashTable(bks, bkc, ukeys, krs, krc, rids, skeys, num_keys)


CoProcessor.phj = PhjCoProcessorMixin.phj
CoProcessor._partition_side_cooperative = \
    PhjCoProcessorMixin._partition_side_cooperative
CoProcessor.basic_unit_shj = PhjCoProcessorMixin.basic_unit_shj
