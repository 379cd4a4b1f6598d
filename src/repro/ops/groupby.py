"""Co-processed hash group-by aggregation (the join's sibling operator).

Group-by shares the join's partition/probe cost structure (Shanbhag et
al.): cluster the group keys with the SAME fused radix passes PHJ uses
(n1+n2 one-VMEM-pass, stable-sort n3), then reduce each partition's
VMEM-resident tuples.  The co-processing skeleton mirrors ``CoProcessor.
phj`` one-to-one:

  * **partition phase** — the key relation is ratio-split between the C
    and G groups (``partition_ratio``), each side runs the planner-chosen
    pass schedule through the fused data path;
  * **aggregate phase** — partitions are ownership-split
    (``agg_ratio``: C owns partition ids ``[0, own)``), each group sorts
    its owned tuples by key (the b2 idiom), derives dense group slots from
    boundary flags (b3), and reduces count/sum/min/max in one pass through
    ``repro.kernels.agg`` — the aggregation analogue of the per-partition
    SHJ.  Identical keys land in one partition, so the two groups' group
    lists are disjoint and concatenate without a merge.

``schedule=None`` skips partitioning entirely (small inputs: the sort *is*
the hash table).  ``agg_ratio`` 0 or 1 then runs the whole relation on one
group (the CPU_ONLY / GPU_ONLY schemes); a fractional ratio row-splits the
relation — each group builds a *partial* group list on its share
concurrently (async dispatch overlaps the two programs) and the partials
merge on the host, the paper's separate-tables-plus-merge mode (Fig. 3)
applied to aggregation (local/global two-phase aggregation; the merge is
O(groups), cheap whenever groups ≪ tuples).  The planner prices all three
against the partitioned DD split.

Semantics: sums (and avg numerators) accumulate *wide* by default — exact
int64, carried through the device path as the segmented-agg kernel's
five-channel int32 layout since TPUs (and jax with x64 disabled) have no
native int64 — so large-value workloads no longer silently wrap.
``wrap32=True`` restores the legacy wrapping-int32 accumulator; the NumPy
oracle (``groupby_ref``) reproduces either mode exactly.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.coprocess import CoProcessor, Timing, _round_up
from repro.core.hash_table import INVALID
from repro.core.relation import Relation, radix_of
from repro.kernels.agg import segmented_aggregate, wide_sums_to_int64

# Pad sentinel for group-key relations: never collides with the join-side
# sentinels (-2/-3) or the executor fill keys (-6/-7); pads carry
# rid == INVALID, which is what actually excludes them from aggregation.
GROUP_PAD_KEY = -4

INT32_MAX = 2**31 - 1
INT32_MIN = -(2**31)


@dataclasses.dataclass
class GroupByResult:
    """Host-side group list: one row per distinct key, unordered."""

    keys: np.ndarray       # (g,) int32 distinct group keys
    counts: np.ndarray     # (g,) int32 tuples per group
    sums: np.ndarray       # (g,) int64 exact sums (int32 wrap under wrap32)
    mins: np.ndarray       # (g,) int32
    maxs: np.ndarray       # (g,) int32

    @property
    def num_groups(self) -> int:
        return int(self.keys.shape[0])

    def sorted(self) -> "GroupByResult":
        """Key-ascending copy (canonical order for comparisons)."""
        o = np.argsort(self.keys, kind="stable")
        return GroupByResult(self.keys[o], self.counts[o], self.sums[o],
                             self.mins[o], self.maxs[o])

    def avgs(self) -> np.ndarray:
        """float64 means from the sums (exact by default, wrapped under
        ``wrap32``) — matches the oracle's mode."""
        return self.sums.astype(np.float64) / np.maximum(self.counts, 1)


@partial(jax.jit, static_argnames=("num_slots", "use_pallas", "interpret",
                                   "wrap32", "mesh"))
def grouped_agg(rel: Relation, values: jax.Array, *, num_slots: int,
                use_pallas: bool | None = None, interpret: bool = False,
                wrap32: bool = False, mesh=None):
    """One group's aggregation: sort by key, flag boundaries, reduce.

    ``values[i]`` belongs to tuple ``i`` of ``rel``; pad tuples are marked
    by ``rid == INVALID`` and contribute nothing.  Returns padded
    ``(ukeys, count, sum, min, max, num_groups)`` — slot ``g`` holds the
    ``g``-th distinct key in (uint32) sorted order; slots past
    ``num_groups`` report count 0.  ``sum`` is the kernel's (5, slots)
    wide-channel layout by default (``wide_sums_to_int64`` decodes) or a
    wrapping int32 vector under ``wrap32=True``.  ``mesh`` names the
    device mesh ``rel`` is sharded over (see ``segmented_aggregate``).
    """
    n = rel.key.shape[0]
    order = jnp.argsort(rel.key.astype(jnp.uint32), stable=True)
    skey = rel.key[order]
    svals = values[order]
    valid = rel.rid[order] != INVALID
    first = (jnp.concatenate([jnp.ones((1,), jnp.bool_),
                              skey[1:] != skey[:-1]])
             if n > 0 else jnp.zeros((0,), jnp.bool_))
    gid = jnp.cumsum(first.astype(jnp.int32)) - 1
    ukeys = jnp.full((num_slots,), GROUP_PAD_KEY,
                     jnp.int32).at[jnp.clip(gid, 0, num_slots - 1)].set(skey)
    cnt, sm, mn, mx = segmented_aggregate(
        jnp.where(valid, gid, -1), svals, num_slots=num_slots,
        use_pallas=use_pallas, interpret=interpret, wrap32=wrap32,
        mesh=mesh)
    num_groups = (first & valid).astype(jnp.int32).sum()
    return ukeys, cnt, sm, mn, mx, num_groups


def _gather_values(values: jax.Array, rid: jax.Array) -> jax.Array:
    """values[rid] with pad rows (rid == -1) mapped to 0, on the device."""
    safe = jnp.clip(rid, 0, max(values.shape[0] - 1, 0))
    out = (jnp.take(values, safe, axis=0) if values.shape[0]
           else jnp.zeros_like(rid))
    return jnp.where(rid >= 0, out, 0).astype(jnp.int32)


def _pull_groups(bnd, outs) -> list:
    """Each group's padded group list, pulled to the host."""
    return bnd.pull(outs, site="groupby.collect", cause="result",
                    column="groups")


def _merge_partials(a: GroupByResult, b: GroupByResult) -> GroupByResult:
    """Global aggregation of two partial group lists (separate + merge).

    Row-split partials may share keys; counts/sums add (wide int64 sums
    add exactly; wrap32 partials add in int32 modular arithmetic,
    associative with the per-group wrap), mins/maxs fold.  O(total
    partial groups) on the host.
    """
    keys = np.concatenate([a.keys, b.keys])
    uk, inv = np.unique(keys, return_inverse=True)
    g = uk.shape[0]
    cnt = np.zeros(g, np.int64)
    np.add.at(cnt, inv, np.concatenate([a.counts, b.counts]).astype(np.int64))
    sm = np.zeros(g, np.int64)
    np.add.at(sm, inv, np.concatenate([a.sums, b.sums]).astype(np.int64))
    mn = np.full(g, INT32_MAX, np.int64)
    np.minimum.at(mn, inv, np.concatenate([a.mins, b.mins]).astype(np.int64))
    mx = np.full(g, INT32_MIN, np.int64)
    np.maximum.at(mx, inv, np.concatenate([a.maxs, b.maxs]).astype(np.int64))
    sum_dtype = (np.int64 if a.sums.dtype == np.int64
                 or b.sums.dtype == np.int64 else np.int32)
    return GroupByResult(uk.astype(np.int32), cnt.astype(np.int32),
                         sm.astype(sum_dtype), mn.astype(np.int32),
                         mx.astype(np.int32))


def _collect(pieces, wrap32: bool = True) -> GroupByResult:
    """Concatenate per-group device results, dropping empty slots.

    Wide pieces carry sums as (5, slots) chunk channels; they decode to
    exact int64 here (host side, O(groups)).
    """
    keys, cnts, sms, mns, mxs = [], [], [], [], []
    for ukeys, cnt, sm, mn, mx, _ in pieces:
        cnt = np.asarray(cnt)
        live = cnt > 0
        sm = np.asarray(sm)
        sm = sm[live] if sm.ndim == 1 else wide_sums_to_int64(sm)[live]
        keys.append(np.asarray(ukeys)[live])
        cnts.append(cnt[live])
        sms.append(sm)
        mns.append(np.asarray(mn)[live])
        mxs.append(np.asarray(mx)[live])
    sum_dtype = np.int32 if wrap32 else np.int64
    cat = lambda xs, dt=np.int32: (np.concatenate(xs) if xs
                                   else np.zeros(0, dt)).astype(dt)
    return GroupByResult(cat(keys), cat(cnts), cat(sms, sum_dtype),
                         cat(mns), cat(mxs))


def groupby_coprocessed(cp: CoProcessor, rel: Relation, values, *,
                        schedule: tuple[int, ...] | None = None,
                        partition_ratio: float = 1.0, agg_ratio: float = 1.0,
                        interpret: bool = False, wrap32: bool = False,
                        ctx=None) -> tuple[GroupByResult, Timing]:
    """Hash group-by of ``values`` by ``rel.key`` across the two groups.

    ``rel.rid`` must index rows of ``values`` (the arange gather
    convention); rid ``INVALID`` marks pad tuples.  ``values`` may be a
    host array or a device array (the fused pipeline hands the sink its
    value column device-resident).  Sums are exact int64 unless
    ``wrap32=True`` requests the legacy int32 wrap.  ``ctx`` (a
    ``QueryContext``) makes the partition phase preemptible —
    pass-at-a-time with ``ctx.check`` at every boundary and once more
    before each aggregate phase.  See module docstring for the phase
    structure.
    """
    from repro.core.partition import radix_partition_scheduled

    timing = Timing(tracer=cp.tracer)
    bnd = cp.boundary
    if rel.size == 0:
        timing.phase_s["partition"] = 0.0
        timing.phase_s["agg"] = 0.0
        return _collect([], wrap32=wrap32), timing
    if isinstance(values, jax.Array):
        values = values.astype(jnp.int32)
    else:
        # A host value column crosses once; every gather below runs on
        # the device.
        values = bnd.push(
            np.ascontiguousarray(np.asarray(values, dtype=np.int32)),
            jnp.asarray, site="groupby.values", cause="base_upload",
            column="values")
    rel = cp.pad_relation(rel, GROUP_PAD_KEY)
    if schedule:
        timing.notes["schedule"] = list(schedule)
        total_bits = sum(schedule)

        def part_fn(r, mesh):
            return radix_partition_scheduled(r, schedule=schedule,
                                             interpret=interpret,
                                             mesh=mesh).rel

        with timing.phase("partition", passes=len(schedule)):
            if ctx is not None:
                rel = cp._partition_side_cooperative(
                    "GB", rel, tuple(schedule), partition_ratio, ctx, 0,
                    timing, interpret=interpret)
            else:
                n = rel.size
                cut = cp._cut(n, partition_ratio)
                if cp.discrete and 0 < cut < n:
                    cp._bus_delay((n - cut) * 8, timing)
                pieces = []
                if cut > 0:
                    f = cp.c.jit(("gb_part", cut, schedule),
                                 partial(part_fn, mesh=cp.c.mesh))
                    pieces.append(f(cp.c.put_items(rel.take(0, cut))))
                if cut < n:
                    f = cp.g.jit(("gb_part", n - cut, schedule),
                                 partial(part_fn, mesh=cp.g.mesh))
                    pieces.append(f(cp.g.put_items(rel.take(cut, n))))
                pieces = bnd.pull(pieces, site="groupby.partition",
                                  cause="exchange", column="rid+key")
                rel = bnd.push(
                    pieces, lambda ps: Relation(
                        jnp.concatenate([x.rid for x in ps]),
                        jnp.concatenate([x.key for x in ps])),
                    site="groupby.partition", cause="exchange",
                    column="rid+key")
        if ctx is not None:
            ctx.check("agg")
        with timing.phase("agg"):
            # Ownership exchange: partitions [0, own) -> C, rest -> G
            # (phj's join-phase split, applied to the reduce).
            num_parts = 1 << total_bits
            own = cp._cut(num_parts, agg_ratio)
            pid = radix_of(rel.key, shift=0, bits=total_bits)
            pid_host = bnd.pull(pid, site="groupby.exchange.pid",
                                cause="exchange", column="pid")
            rid_host, key_host = bnd.pull(
                (rel.rid, rel.key), site="groupby.exchange.rows",
                cause="exchange", column="rid+key")
            outs = []
            for grp, mask in ((cp.c, pid_host < own),
                              (cp.g, pid_host >= own)):
                if (own == 0 and grp is cp.c) or (own == num_parts
                                                  and grp is cp.g):
                    continue
                idx = np.nonzero(mask)[0]
                m = _round_up(max(len(idx), 1), cp.lcm)
                rid = np.full(m, int(INVALID), np.int32)
                key = np.full(m, GROUP_PAD_KEY, np.int32)
                rid[:len(idx)] = rid_host[idx]
                key[:len(idx)] = key_host[idx]
                if cp.discrete:
                    cp._bus_delay(len(idx) * 8 // 2, timing)
                # The share crosses to its group once; its own rids
                # gather its values from the group's copy of the column.
                share = bnd.push(
                    Relation(rid, key), grp.put_items,
                    site="groupby.exchange.share", cause="exchange",
                    column="rid+key")
                vals = _gather_values(grp.put_shared(values), share.rid)
                f = grp.jit(("gb_agg", m, interpret, wrap32),
                            partial(grouped_agg, num_slots=m,
                                    interpret=interpret, wrap32=wrap32,
                                    mesh=grp.mesh))
                outs.append(f(share, grp.put_items(vals)))
            result = _collect(_pull_groups(bnd, outs), wrap32=wrap32)
    else:
        timing.phase_s["partition"] = 0.0
        if ctx is not None:
            ctx.check("agg")
        with timing.phase("agg"):
            n = rel.size
            cut = cp._cut(n, agg_ratio)
            if 0 < cut < n:
                # Separate partial aggregation + host merge: each group
                # builds a partial group list on its row share (both
                # programs in flight at once — async dispatch), merged
                # below.
                if cp.discrete:
                    cp._bus_delay((n - cut) * 8, timing)
                vals = _gather_values(values, rel.rid)
                outs = []
                for grp, lo, hi in ((cp.c, 0, cut), (cp.g, cut, n)):
                    f = grp.jit(("gb_agg", hi - lo, interpret, wrap32),
                                partial(grouped_agg, num_slots=hi - lo,
                                        interpret=interpret, wrap32=wrap32,
                                        mesh=grp.mesh))
                    outs.append(f(grp.put_items(rel.take(lo, hi)),
                                  grp.put_items(vals[lo:hi])))
            else:
                grp = cp.c if cut == n else cp.g
                if cp.discrete and grp is cp.g:
                    cp._bus_delay(n * 8, timing)
                vals = _gather_values(values, rel.rid)
                f = grp.jit(("gb_agg", n, interpret, wrap32),
                            partial(grouped_agg, num_slots=n,
                                    interpret=interpret, wrap32=wrap32,
                                    mesh=grp.mesh))
                outs = [f(grp.put_items(rel), grp.put_items(vals))]
            outs = _pull_groups(bnd, outs)
            if len(outs) == 2:
                with cp.tracer.span("merge"):
                    result = _merge_partials(
                        _collect(outs[:1], wrap32=wrap32),
                        _collect(outs[1:], wrap32=wrap32))
            else:
                result = _collect(outs, wrap32=wrap32)
    timing.wall_s = timing.phase_s["partition"] + timing.phase_s["agg"]
    timing.notes["num_groups"] = result.num_groups
    return result, timing


# ---------------------------------------------------------------------------
# NumPy oracle (testing/verification only).
# ---------------------------------------------------------------------------

def groupby_ref(keys, values, *, wrap32: bool = False) -> GroupByResult:
    """Exact group-by oracle: key-sorted groups.

    Sums are exact int64 by default; ``wrap32=True`` reproduces the legacy
    int32-wrapping device accumulator exactly.
    """
    keys = np.asarray(keys)
    values = np.asarray(values, dtype=np.int64)
    uk, inv = np.unique(keys, return_inverse=True)
    g = uk.shape[0]
    cnt = np.bincount(inv, minlength=g).astype(np.int32)
    sm = np.zeros(g, np.int64)
    np.add.at(sm, inv, values)
    mn = np.full(g, INT32_MAX, np.int64)
    np.minimum.at(mn, inv, values)
    mx = np.full(g, INT32_MIN, np.int64)
    np.maximum.at(mx, inv, values)
    return GroupByResult(uk.astype(np.int32), cnt,
                         sm.astype(np.int32) if wrap32 else sm,
                         mn.astype(np.int32), mx.astype(np.int32))


CoProcessor.groupby = groupby_coprocessed
