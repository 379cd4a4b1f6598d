"""Hash-join engine correctness: unit + hypothesis property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import (CoProcessor, Relation, build_hash_table,
                        default_num_buckets, join_oracle, murmur3_fmix32,
                        phj_join, probe_hash_table, probe_with_selectivity,
                        shj_join, skewed_relation, uniform_relation,
                        unique_relation)
from repro.core import hash_table as ht
from repro.core.hash_table import merge_hash_tables
from repro.core.partition import radix_partition, partition_ids
from repro.core.phj import phj_coarse_join


def _check_join(build, probe, num_buckets=None, max_out=None):
    exp = join_oracle(build, probe)
    nb = num_buckets or default_num_buckets(build.size)
    mo = max_out or max(64, 4 * (len(exp) + 8))
    res = shj_join(build, probe, num_buckets=nb, max_out=mo)
    got = res.valid_pairs()
    assert got.shape == exp.shape
    assert (got == exp).all()
    return exp


def test_shj_unique_keys():
    _check_join(unique_relation(1000, seed=1),
                uniform_relation(3000, key_range=1500, seed=2))


def test_shj_duplicate_build_keys():
    _check_join(uniform_relation(2000, key_range=300, seed=3),
                uniform_relation(1000, key_range=300, seed=4))


def test_shj_skewed():
    _check_join(skewed_relation(2000, s_percent=25, seed=5),
                skewed_relation(3000, s_percent=25, seed=6))


def test_shj_no_matches():
    b = Relation(jnp.arange(100), jnp.arange(100))
    p = Relation(jnp.arange(50), jnp.arange(50) + 1000)
    res = shj_join(b, p, num_buckets=32, max_out=64)
    assert int(res.count) == 0


def test_shj_selectivity():
    b = unique_relation(1000, seed=7)
    for sel in (0.125, 0.5, 1.0):
        p = probe_with_selectivity(b, 2000, selectivity=sel, seed=8)
        exp = _check_join(b, p)
        assert abs(len(exp) / 2000 - sel) < 0.05


def test_phj_matches_shj():
    b = uniform_relation(4096, key_range=1000, seed=9)
    p = uniform_relation(8192, key_range=1000, seed=10)
    exp = join_oracle(b, p)
    res = phj_join(b, p, bits_per_pass=3, num_passes=2, buckets_per_part=8,
                   max_out=4 * len(exp))
    assert (res.valid_pairs() == exp).all()


def test_phj_coarse_matches():
    bits = 4
    b = uniform_relation(2048, key_range=700, seed=11)
    p = uniform_relation(4096, key_range=700, seed=12)
    exp = join_oracle(b, p)
    pr = radix_partition(b, bits_per_pass=2, num_passes=2)
    ps = radix_partition(p, bits_per_pass=2, num_passes=2)
    cap = int(max(np.asarray(pr.part_count).max(),
                  np.asarray(ps.part_count).max())) + 8
    res = phj_coarse_join(pr, ps, num_parts=1 << bits, part_cap=cap,
                          buckets_per_part=16,
                          max_out_per_part=cap * 16)
    assert (res.valid_pairs() == exp).all()


def test_merge_partial_tables():
    b = uniform_relation(2048, key_range=512, seed=13)
    p = uniform_relation(2048, key_range=512, seed=14)
    nb = 256
    t1 = build_hash_table(b.take(0, 1024), nb)
    t2 = build_hash_table(b.take(1024, 2048), nb)
    merged = merge_hash_tables([t1, t2], nb)
    res = probe_hash_table(p, merged, 65536)
    assert (res.valid_pairs() == join_oracle(b, p)).all()


def test_output_capacity_truncation():
    b = uniform_relation(512, key_range=4, seed=15)   # heavy duplication
    p = uniform_relation(512, key_range=4, seed=16)
    res = shj_join(b, p, num_buckets=16, max_out=100)
    assert int(res.count) == 100   # truncated, reported honestly
    assert (np.asarray(res.probe_rid[:100]) >= 0).all()


# ---------------------------------------------------------------------------
# p3's key-list search: bounded by the widest key list the table holds.
# ---------------------------------------------------------------------------

def _probe_p3_full_width(table, key, kstart, kcount):
    """The search as it ran before its bound: log2 of the whole table."""
    n = table.ukeys.shape[0]
    lo, hi = kstart, kstart + kcount
    target = key.astype(jnp.uint32)

    def body(_, lh):
        lo, hi = lh
        mid = (lo + hi) >> 1
        mid_key = table.ukeys[jnp.clip(mid, 0, n - 1)].astype(jnp.uint32)
        go_right = (mid_key < target) & (lo < hi)
        return (jnp.where(go_right, mid + 1, lo),
                jnp.where(go_right | (lo >= hi), hi, mid))

    lo, _ = jax.lax.fori_loop(0, max(1, int(n).bit_length() + 1), body,
                              (lo, hi))
    pos = jnp.clip(lo, 0, n - 1)
    found = (lo < kstart + kcount) & (table.ukeys[pos] == key)
    return (jnp.where(found, pos, -1),
            jnp.where(found, table.key_rid_count[pos], 0))


def _rel(keys):
    keys = np.asarray(keys, np.int32)
    return Relation(jnp.arange(keys.size, dtype=jnp.int32), jnp.asarray(keys))


def _empty_table(num_buckets, capacity):
    """A table with no key entries, as b3 leaves every unused slot."""
    return ht.HashTable(
        jnp.zeros((num_buckets,), jnp.int32),
        jnp.zeros((num_buckets,), jnp.int32),
        jnp.full((capacity,), ht.INVALID),
        jnp.full((capacity,), capacity, jnp.int32),
        jnp.zeros((capacity,), jnp.int32),
        jnp.full((capacity,), ht.INVALID),
        jnp.full((capacity,), ht.INVALID),
        jnp.int32(0))


def _p3_case(name):
    """(build relation or None for empty, probe relation, buckets, least
    trip count the case must reach)."""
    rng = np.random.default_rng(sum(map(ord, name)))
    if name == "uniform":          # ~4 tuples a bucket, the cells' sizing
        b = rng.integers(0, 1 << 20, 4096)
        p = np.concatenate([rng.choice(b, 3000), rng.integers(0, 1 << 20,
                                                              1000)])
        return _rel(b), _rel(p), default_num_buckets(4096), 1
    if name == "four_wide_buckets":  # hundreds of keys a key list
        b = rng.permutation(2000)
        p = rng.integers(-50, 2100, 3000)
        return _rel(b), _rel(p), 4, 9
    if name == "empty":
        return None, _rel(rng.integers(-4, 100, 500)), 64, 0
    if name == "hot_key":          # one key with many rids widens no list
        b = np.concatenate([np.full(700, 12345), rng.integers(0, 5000, 300)])
        p = np.concatenate([np.full(40, 12345), rng.integers(0, 5000, 400)])
        return _rel(rng.permutation(b)), _rel(p), 256, 1
    if name == "pad_keys":         # PHJ's build pad -2 among real keys
        b = np.concatenate([rng.integers(0, 3000, 900), np.full(124, -2)])
        p = np.concatenate([rng.integers(0, 3000, 600),
                            [-3, -1, -2, -7, np.iinfo(np.int32).min] * 8])
        return _rel(b), _rel(p), 256, 1
    raise KeyError(name)


P3_CASES = ["uniform", "four_wide_buckets", "empty", "hot_key", "pad_keys"]


@pytest.mark.parametrize("case", P3_CASES)
def test_probe_p3_matches_full_width_search(case):
    build, probe, nb, _ = _p3_case(case)
    table = (_empty_table(nb, 512) if build is None
             else build_hash_table(build, nb))
    kstart, kcount = ht.probe_p2(table, ht.probe_p1(probe.key, nb))
    entry, nmatch = jax.jit(ht.probe_p3)(table, probe.key, kstart, kcount)
    ref_entry, ref_nmatch = _probe_p3_full_width(table, probe.key, kstart,
                                                 kcount)
    assert np.array_equal(entry, ref_entry)
    assert np.array_equal(nmatch, ref_nmatch)
    if build is None:
        assert int(table.num_keys) == 0 and not np.asarray(nmatch).any()
        return
    exp = join_oracle(build, probe)
    res = probe_hash_table(probe, table, max(64, len(exp) + 8))
    assert np.array_equal(res.valid_pairs(), exp)


@pytest.mark.parametrize("case", P3_CASES)
def test_p3_trip_count_is_widest_key_list(case):
    build, _, nb, least = _p3_case(case)
    table = (_empty_table(nb, 512) if build is None
             else build_hash_table(build, nb))
    widest = int(np.asarray(table.bucket_key_count).max())
    trips = int(jax.jit(ht.p3_trip_count)(table))
    assert trips == widest.bit_length()
    assert trips >= least


def test_phj_coprocess_colliding_keys_pair_exact():
    """CoProcessor.phj's owned join on keys that share a bucket: the
    share's key-list bound passes 4 and the pairs stay exact."""
    bits_per_pass, num_passes, shj_bits = 3, 2, 2
    buckets = 1 << (bits_per_pass * num_passes + shj_bits)
    cand = np.arange(1 << 17, dtype=np.int32)
    h = np.asarray(murmur3_fmix32(jnp.asarray(cand)))
    colliding = cand[(h & (buckets - 1)) == 0][:300]
    rng = np.random.default_rng(21)
    bkeys = np.concatenate([colliding, rng.integers(1 << 17, 1 << 20, 1700)])
    pkeys = np.concatenate([rng.choice(bkeys, 3000),
                            rng.integers(0, 1 << 20, 1096)])
    b, p = _rel(rng.permutation(bkeys)), _rel(pkeys)
    # The owned join's bucket is a bit permutation of the hash's low
    # (total + shj) bits, so it has the widest list build_hash_table has.
    assert int(ht.p3_trip_count(build_hash_table(b, buckets))) > 4
    exp = join_oracle(b, p)
    res, _ = CoProcessor().phj(b, p, bits_per_pass=bits_per_pass,
                               num_passes=num_passes, shj_bits=shj_bits,
                               max_out=8192, partition_ratio=0.25,
                               join_ratio=0.5)
    assert np.array_equal(res.valid_pairs(), exp)


# ---------------------------------------------------------------------------
# Property tests.
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(
    nb=st.integers(1, 400), np_=st.integers(1, 400),
    key_range=st.integers(1, 500), seed=st.integers(0, 2**31 - 1),
)
def test_property_join_equals_oracle(nb, np_, key_range, seed):
    rng = np.random.default_rng(seed)
    b = Relation(jnp.arange(nb, dtype=jnp.int32),
                 jnp.asarray(rng.integers(0, key_range, nb, dtype=np.int32)))
    p = Relation(jnp.arange(np_, dtype=jnp.int32),
                 jnp.asarray(rng.integers(0, key_range, np_,
                                          dtype=np.int32)))
    exp = join_oracle(b, p)
    res = shj_join(b, p, num_buckets=64, max_out=max(64, 4 * len(exp) + 8))
    assert (res.valid_pairs() == exp).all()


@settings(max_examples=25, deadline=None)
@given(n=st.integers(1, 2000), bits=st.integers(1, 6),
       passes=st.integers(1, 3), seed=st.integers(0, 2**31 - 1))
def test_property_partition_complete_and_clustered(n, bits, passes, seed):
    """Radix partitioning is a permutation AND clusters by partition id."""
    rng = np.random.default_rng(seed)
    rel = Relation(jnp.arange(n, dtype=jnp.int32),
                   jnp.asarray(rng.integers(0, 1 << 30, n, dtype=np.int32)))
    parts = radix_partition(rel, bits_per_pass=bits, num_passes=passes)
    # permutation: same multiset of (rid, key)
    got = np.stack([np.asarray(parts.rel.rid), np.asarray(parts.rel.key)], 1)
    exp = np.stack([np.asarray(rel.rid), np.asarray(rel.key)], 1)
    assert (got[np.lexsort(got.T)] == exp[np.lexsort(exp.T)]).all()
    # clustered: pids non-decreasing; headers consistent
    pid = np.asarray(partition_ids(parts.rel, total_bits=bits * passes))
    assert (np.diff(pid) >= 0).all()
    counts = np.asarray(parts.part_count)
    assert counts.sum() == n
    assert (np.asarray(parts.part_start)
            == np.concatenate([[0], np.cumsum(counts)[:-1]])).all()


@settings(max_examples=20, deadline=None)
@given(n=st.integers(1, 512), dup=st.integers(1, 50),
       seed=st.integers(0, 2**31 - 1))
def test_property_build_table_invariants(n, dup, seed):
    """Bucket headers tile the key entries; rid lists cover every tuple."""
    rng = np.random.default_rng(seed)
    rel = Relation(jnp.arange(n, dtype=jnp.int32),
                   jnp.asarray(rng.integers(0, dup, n, dtype=np.int32)))
    nb = 32
    t = build_hash_table(rel, nb)
    nk = int(t.num_keys)
    assert nk == len(np.unique(np.asarray(rel.key)))
    bks = np.asarray(t.bucket_key_start)
    bkc = np.asarray(t.bucket_key_count)
    assert bkc.sum() == nk
    assert (np.asarray(t.key_rid_count)[:nk].sum()) == n
