"""Dense bucketed hash table — the paper's two-level hash table, TPU-native.

Paper §3.1: "A hash table consists of an array of bucket headers ... the
pointer to a key list.  The key list contains all the unique keys with the
same hash value, each of which links a *rid* list storing the IDs for all
tuples with the same key."

Pointer chasing is hostile to TPU vector units, so we materialize the exact
same three-level structure (bucket header -> key list -> rid list) as dense
CSR-style arrays, built with sorts + scans instead of latched inserts (see
DESIGN.md §2: the scan is the TPU-idiomatic replacement for the paper's
atomic-based allocator).  The logical structure, and the per-step access
pattern of build (b1..b4) and probe (p1..p4), are preserved one-to-one:

  build   b1: compute hash bucket number          (VPU ALU map)
          b2: visit the hash bucket header         (histogram + scan = "allocator")
          b3: visit key lists / create key headers (stable sort + boundary flags)
          b4: insert record id into the rid list   (scatter in sorted order)
  probe   p1: compute hash bucket number          (VPU ALU map)
          p2: visit the hash bucket header         (1 random gather / tuple)
          p3: visit the hash key lists             (log2(bucket keys) gathers / tuple)
          p4: visit matching build tuple, emit     (expand via scan + gathers)

Every function is shape-static and jit-compatible; data-dependent sizes
(number of unique keys, number of matches) are carried as scalars next to
padded arrays.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .relation import Relation, bucket_of, next_pow2

INVALID = jnp.int32(-1)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class HashTable:
    """CSR form of the paper's bucket-header -> key-list -> rid-list table."""

    # -- bucket headers (paper: "array of bucket headers") ------------------
    bucket_key_start: jax.Array  # (B,) index of the bucket's first key entry
    bucket_key_count: jax.Array  # (B,) number of unique keys in the bucket
    # -- key list (paper: "all the unique keys with the same hash value") ---
    ukeys: jax.Array             # (n,) unique keys, sorted by (bucket, key); padded
    key_rid_start: jax.Array     # (n,) index of the key's first rid
    key_rid_count: jax.Array     # (n,) number of rids under the key
    # -- rid list ------------------------------------------------------------
    rids: jax.Array              # (n,) rids, grouped by (bucket, key)
    skeys: jax.Array             # (n,) key value per rid slot (sorted order)
    num_keys: jax.Array          # scalar int32: number of valid key entries

    @property
    def num_buckets(self) -> int:
        return int(self.bucket_key_start.shape[0])

    @property
    def capacity(self) -> int:
        return int(self.rids.shape[0])

    def tree_flatten(self):
        fields = (self.bucket_key_start, self.bucket_key_count, self.ukeys,
                  self.key_rid_start, self.key_rid_count, self.rids,
                  self.skeys, self.num_keys)
        return fields, None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class JoinResult:
    """Matching ``(probe_rid, build_rid)`` pairs, padded with -1."""

    probe_rid: jax.Array
    build_rid: jax.Array
    count: jax.Array  # scalar int32: number of valid pairs

    def tree_flatten(self):
        return (self.probe_rid, self.build_rid, self.count), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    def valid_pairs(self) -> np.ndarray:
        """Host-side (count, 2) array of valid pairs, sorted — for testing."""
        c = int(self.count)
        pairs = np.stack([np.asarray(self.probe_rid[:c]),
                          np.asarray(self.build_rid[:c])], axis=1)
        return pairs[np.lexsort((pairs[:, 1], pairs[:, 0]))]


def default_num_buckets(n: int, *, avg_bucket: int = 4) -> int:
    """Paper-style sizing: a few tuples per bucket on average, power of two."""
    return max(4, next_pow2(max(1, n // avg_bucket)))


# ---------------------------------------------------------------------------
# Build phase, as the fine-grained steps b1..b4.
# ---------------------------------------------------------------------------

def build_b1(key: jax.Array, num_buckets: int) -> jax.Array:
    """(b1) compute hash bucket number."""
    return bucket_of(key, num_buckets)


def build_b2_order(bkt: jax.Array, key: jax.Array) -> jax.Array:
    """(b2) bucket-header placement: stable (bucket, key) order.

    Two stable argsorts give lexicographic (bucket, key) order — this is the
    scan-based equivalent of walking each tuple to its bucket header and
    claiming a slot with the paper's block allocator.
    """
    order = jnp.argsort(key.astype(jnp.uint32), stable=True)
    order = order[jnp.argsort(bkt[order], stable=True)]
    return order


def build_b3_keylists(sbkt: jax.Array, skey: jax.Array, num_buckets: int):
    """(b3) create key headers: boundary flags over the sorted tuples."""
    first = jnp.concatenate([
        jnp.ones((1,), jnp.bool_),
        (sbkt[1:] != sbkt[:-1]) | (skey[1:] != skey[:-1]),
    ])
    key_id = jnp.cumsum(first.astype(jnp.int32)) - 1          # per-tuple key entry
    num_keys = first.astype(jnp.int32).sum()
    n = skey.shape[0]
    iota = jnp.arange(n, dtype=jnp.int32)
    ukeys = jnp.full((n,), INVALID).at[key_id].set(skey)
    key_rid_start = jnp.full((n,), n, jnp.int32).at[key_id].min(iota)
    key_rid_count = jax.ops.segment_sum(jnp.ones((n,), jnp.int32), key_id,
                                        num_segments=n)
    # Bucket headers count unique keys (= first flags) per bucket.
    bucket_key_count = jax.ops.segment_sum(first.astype(jnp.int32), sbkt,
                                           num_segments=num_buckets)
    bucket_key_start = jnp.cumsum(bucket_key_count) - bucket_key_count
    return (ukeys, key_rid_start, key_rid_count, bucket_key_start,
            bucket_key_count, num_keys)


def build_b4_ridlists(rid: jax.Array, order: jax.Array) -> jax.Array:
    """(b4) insert record ids into the rid lists (gather in sorted order)."""
    return rid[order]


@partial(jax.jit, static_argnames=("num_buckets",))
def build_hash_table(rel: Relation, num_buckets: int) -> HashTable:
    """Full build phase: b1 -> b2 -> b3 -> b4."""
    bkt = build_b1(rel.key, num_buckets)
    order = build_b2_order(bkt, rel.key)
    sbkt, skey = bkt[order], rel.key[order]
    (ukeys, key_rid_start, key_rid_count, bucket_key_start, bucket_key_count,
     num_keys) = build_b3_keylists(sbkt, skey, num_buckets)
    rids = build_b4_ridlists(rel.rid, order)
    return HashTable(bucket_key_start, bucket_key_count, ukeys, key_rid_start,
                     key_rid_count, rids, skey, num_keys.astype(jnp.int32))


def merge_hash_tables(parts: list[HashTable], num_buckets: int) -> HashTable:
    """Merge partial hash tables (the paper's DD merge step, Fig. 3).

    Separate-table co-processing builds one partial table per processor
    group; merging concatenates the underlying sorted tuple streams and
    rebuilds the CSR structure (a k-way merge; implemented as concat +
    rebuild, which XLA lowers to a single sort — the measured merge cost the
    paper reports as 14–18% of DD time on discrete architectures).
    """
    rid = jnp.concatenate([p.rids for p in parts])
    key = jnp.concatenate([p.skeys for p in parts])
    return build_hash_table(Relation(rid, key), num_buckets)


# ---------------------------------------------------------------------------
# Probe phase, as the fine-grained steps p1..p4.
# ---------------------------------------------------------------------------

def probe_p1(key: jax.Array, num_buckets: int) -> jax.Array:
    """(p1) compute hash bucket number."""
    return bucket_of(key, num_buckets)


def probe_p2(table: HashTable, bkt: jax.Array):
    """(p2) visit the hash bucket header: one random gather per tuple."""
    return table.bucket_key_start[bkt], table.bucket_key_count[bkt]


def p3_trip_count(table: HashTable) -> jax.Array:
    """Iterations p3's search needs: ``bit_length`` of the widest key list.

    Each halving of a range of width ``w`` leaves at most ``w // 2``, so
    ``bit_length(w)`` halvings converge it. A device scalar, computed
    inside the probe's program and never pulled to the host.
    """
    widest = jnp.max(table.bucket_key_count, initial=0)
    return jnp.int32(32) - jax.lax.clz(widest.astype(jnp.int32))


def probe_p3(table: HashTable, key: jax.Array, kstart: jax.Array,
             kcount: jax.Array):
    """(p3) search the bucket's key list: bounded binary search.

    The key list of a bucket is a sorted contiguous segment of ``ukeys``,
    so the paper's list walk becomes a binary search with log2(|keys in
    bucket|) random gathers per tuple (vs. the list walk's O(|keys|)).
    The loop runs ``p3_trip_count(table)`` iterations: the halvings the
    table's widest key list needs, which converge every lane's range.
    Returns the matching key-entry index (or -1) and its rid count.
    """
    n = table.ukeys.shape[0]
    lo = kstart
    hi = kstart + kcount
    target = key.astype(jnp.uint32)

    def body(_, lh):
        lo, hi = lh
        mid = (lo + hi) >> 1
        mid_c = jnp.clip(mid, 0, n - 1)
        mid_key = table.ukeys[mid_c].astype(jnp.uint32)
        go_right = (mid_key < target) & (lo < hi)
        new_lo = jnp.where(go_right, mid + 1, lo)
        new_hi = jnp.where(go_right | (lo >= hi), hi, mid)
        return new_lo, new_hi

    lo, hi = jax.lax.fori_loop(0, p3_trip_count(table), body, (lo, hi))
    pos = jnp.clip(lo, 0, n - 1)
    found = (lo < kstart + kcount) & (table.ukeys[pos] == key)
    entry = jnp.where(found, pos, -1)
    nmatch = jnp.where(found, table.key_rid_count[pos], 0)
    return entry, nmatch


def probe_p4(table: HashTable, probe_rid: jax.Array, entry: jax.Array,
             nmatch: jax.Array, max_out: int) -> JoinResult:
    """(p4) visit matching build tuples and produce output pairs.

    Variable-fanout output is materialized with the scan allocator:
    per-tuple match counts -> exclusive scan -> gather-based expansion.
    ``max_out`` is the static output capacity (the paper's pre-allocated
    result buffer); overflow is truncated and reported via ``count``.
    """
    n = probe_rid.shape[0]
    offs = jnp.cumsum(nmatch)
    total = offs[-1] if n > 0 else jnp.int32(0)
    starts = offs - nmatch
    out_idx = jnp.arange(max_out, dtype=jnp.int32)
    src = jnp.searchsorted(offs, out_idx, side="right").astype(jnp.int32)
    valid = out_idx < jnp.minimum(total, max_out)
    src_c = jnp.clip(src, 0, n - 1)
    j = out_idx - starts[src_c]
    cap = table.rids.shape[0]
    bpos = jnp.clip(table.key_rid_start[jnp.clip(entry[src_c], 0, cap - 1)] + j,
                    0, cap - 1)
    out_build = jnp.where(valid, table.rids[bpos], INVALID)
    out_probe = jnp.where(valid, probe_rid[src_c], INVALID)
    return JoinResult(out_probe, out_build,
                      jnp.minimum(total, max_out).astype(jnp.int32))


@partial(jax.jit, static_argnames=("max_out",))
def probe_hash_table(rel: Relation, table: HashTable, max_out: int) -> JoinResult:
    """Full probe phase: p1 -> p2 -> p3 -> p4."""
    bkt = probe_p1(rel.key, table.num_buckets)
    kstart, kcount = probe_p2(table, bkt)
    entry, nmatch = probe_p3(table, rel.key, kstart, kcount)
    return probe_p4(table, rel.rid, entry, nmatch, max_out)


# ---------------------------------------------------------------------------
# Oracles (testing only; numpy, not jitted).
# ---------------------------------------------------------------------------

def join_oracle(build: Relation, probe: Relation) -> np.ndarray:
    """Sort-merge oracle: all matching (probe_rid, build_rid) pairs, sorted."""
    bk = np.asarray(build.key)
    br = np.asarray(build.rid)
    pk = np.asarray(probe.key)
    pr = np.asarray(probe.rid)
    order_b = np.argsort(bk, kind="stable")
    bk, br = bk[order_b], br[order_b]
    lo = np.searchsorted(bk, pk, side="left")
    hi = np.searchsorted(bk, pk, side="right")
    counts = hi - lo
    # Pair j of probe i sits at output row starts[i] + j.
    probe_of = np.repeat(np.arange(pk.shape[0]), counts)
    starts = np.cumsum(counts) - counts
    within = np.arange(probe_of.shape[0]) - starts[probe_of]
    out = np.stack([pr[probe_of].astype(np.int64),
                    br[lo[probe_of] + within].astype(np.int64)], axis=1)
    return out[np.lexsort((out[:, 1], out[:, 0]))]
