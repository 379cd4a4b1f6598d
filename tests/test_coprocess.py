"""Two-group co-processing executor: every scheme produces the oracle join
(single-device degraded mode here; the real 8-device run is exercised by
the benchmark harness and by test_multidevice.py's subprocess)."""
import numpy as np
import pytest

from repro.core import (CoProcessor, PCIE_LINK, join_oracle,
                        uniform_relation, unique_relation)


@pytest.fixture(scope="module")
def data():
    b = unique_relation(2048, seed=1)
    p = uniform_relation(4096, key_range=3000, seed=2)
    return b, p, join_oracle(b, p)


@pytest.fixture(scope="module")
def cp():
    return CoProcessor()


SCHEMES = {
    "cpu_only": ([1.0] * 4, [1.0] * 4),
    "gpu_only": ([0.0] * 4, [0.0] * 4),
    "dd": ([0.25] * 4, [0.5] * 4),
    "pl": ([0.0, 0.25, 0.5, 0.25], [0.0, 0.25, 0.75, 0.25]),
}


@pytest.mark.parametrize("mode", ["shared", "separate"])
@pytest.mark.parametrize("scheme", list(SCHEMES))
def test_shj_schemes(data, cp, mode, scheme):
    b, p, exp = data
    br, pr = SCHEMES[scheme]
    res, t = cp.shj(b, p, num_buckets=512, max_out=32768,
                    build_ratios=br, probe_ratios=pr, table_mode=mode)
    got = res.valid_pairs()
    assert got.shape == exp.shape
    assert (got == exp).all()
    assert t.wall_s > 0


def test_shj_discrete_emulation(data):
    b, p, exp = data
    cp = CoProcessor(link=PCIE_LINK, discrete=True)
    res, t = cp.shj(b, p, num_buckets=512, max_out=32768,
                    build_ratios=[0.25] * 4, probe_ratios=[0.5] * 4,
                    table_mode="separate")
    assert (res.valid_pairs() == exp).all()
    assert t.transfer_bytes > 0


def test_phj_coprocess(data, cp):
    b, p, exp = data
    res, t = cp.phj(b, p, bits_per_pass=3, num_passes=2, shj_bits=2,
                    max_out=32768, partition_ratio=0.25, join_ratio=0.5)
    assert (res.valid_pairs() == exp).all()
    assert set(t.phase_s) == {"partition", "join"}


def test_basic_unit(data, cp):
    b, p, exp = data
    res, t, ratios = cp.basic_unit_shj(b, p, num_buckets=512,
                                       max_out=32768, chunk=512)
    assert (res.valid_pairs() == exp).all()
    assert 0.0 <= ratios["build"] <= 1.0
    assert 0.0 <= ratios["probe"] <= 1.0


def test_divergence_grouping_roundtrip(rng):
    import jax.numpy as jnp
    from repro.core import (divergence_order, inverse_permutation,
                            tile_divergence_waste)
    w = jnp.asarray(rng.zipf(1.5, 4096).clip(0, 1000).astype(np.int32))
    order = divergence_order(w, num_groups=64)
    inv = inverse_permutation(order)
    assert (np.asarray(order[inv]) == np.arange(4096)).all()
    before = float(tile_divergence_waste(w, tile=256))
    after = float(tile_divergence_waste(w[order], tile=256))
    assert after <= before  # grouping only helps


def test_scan_allocator(rng):
    import jax.numpy as jnp
    from repro.core import alloc_stats, basic_alloc_units, scan_alloc
    sizes = jnp.asarray(rng.integers(0, 9, 4096, dtype=np.int32))
    offs, total = scan_alloc(sizes, tile=256, block_items=256)
    offs = np.asarray(offs)
    sz = np.asarray(sizes)
    # Non-overlapping extents.  Zero-size requests legitimately share an
    # offset with the next live extent, so only positive extents are
    # checked (argsort orders equal offsets arbitrarily).
    pos = sz > 0
    order = np.argsort(offs[pos])
    ends = offs[pos][order] + sz[pos][order]
    assert (offs[pos][order][1:] >= ends[:-1]).all()
    assert int(total) >= sz.sum()
    st = alloc_stats(sizes, tile=256, block_items=256)
    assert st.global_units == 4096 // 256           # one claim per tile
    assert basic_alloc_units(sizes) == int((sz > 0).sum())


@pytest.mark.parametrize("n, own, num_parts, lcm, rows", [
    (4096, 1, 2, 1, 4096),
    (4096, 4, 16, 1, 0),
    (12288, 3, 4, 3, 9216),
    (1 << 15, 4, 16, 1, 20480),
    (1 << 16, 64, 128, 2, 1),
    (1 << 20, 7, 32, 4, 1 << 20),
    (1 << 24, 4096, 8192, 1, 8390429),
    (1 << 24, 4096, 8192, 1, 1 << 24),
    (1 << 24, 1, 8192, 1, 3 << 20),
    (1 << 24, 8191, 8192, 8, 1 << 23),
])
def test_share_capacity(n, own, num_parts, lcm, rows):
    """The share's capacity is fixed before the data: a binomial share
    stays on rung 0, a larger one climbs a few rungs, never past the
    side's size, and no row is ever cut off."""
    import math
    from repro.core.coprocess import _round_up, _share_capacity
    top = _round_up(n, lcm)
    cap, _ = _share_capacity(n, own, num_parts, rows, lcm)
    assert rows <= cap <= top and cap % lcm == 0
    p = own / num_parts
    mean, sigma = n * p, math.sqrt(n * p * (1 - p))
    assert _share_capacity(n, own, num_parts,
                           min(n, int(mean + 64 * sigma)), lcm)[1] == 0
    # Capacity is a step function of rows: walk its steps over [0, n].
    caps, r = [], 0
    while r <= n:
        c, k = _share_capacity(n, own, num_parts, r, lcm)
        assert c >= r and k == len(caps)
        caps.append(c)
        r = c + 1
    assert caps == sorted(set(caps)) and caps[-1] == top
    assert len(caps) <= math.log2(num_parts / own) + 2
