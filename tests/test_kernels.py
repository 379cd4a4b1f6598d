"""Per-kernel interpret-mode sweeps vs pure-jnp oracles."""
import jax.numpy as jnp
import numpy as np
import pytest
from numpy.testing import assert_allclose

from repro.core import uniform_relation, unique_relation


@pytest.mark.parametrize("n,buckets", [(1024, 64), (4096, 256),
                                       (8192, 1024)])
def test_hash_kernel(n, buckets, rng):
    from repro.kernels.hash.hash import hash_bucket_pallas
    from repro.kernels.hash.ref import hash_bucket_ref
    keys = jnp.asarray(rng.integers(0, 2**31 - 1, n, dtype=np.int32))
    got = hash_bucket_pallas(keys, num_buckets=buckets, interpret=True)
    assert (np.asarray(got) == np.asarray(
        hash_bucket_ref(keys, num_buckets=buckets))).all()


@pytest.mark.parametrize("dtype", [np.int32, np.uint32])
@pytest.mark.parametrize("n,bits,shift", [(1024, 4, 0), (4096, 6, 0),
                                          (4096, 3, 6), (8192, 8, 2),
                                          (2048, 1, 31), (16384, 8, 24)])
def test_fused_partition_hist_kernel(n, bits, shift, dtype, rng):
    """Fused n1+n2: pid AND histogram from one VMEM pass == oracle."""
    from repro.kernels.partition_hist.fused import partition_hist_fused_pallas
    from repro.kernels.partition_hist.ref import partition_hist_fused_ref
    keys = jnp.asarray(rng.integers(0, 2**31 - 1, n).astype(dtype))
    pid, hist = partition_hist_fused_pallas(keys, shift=shift, bits=bits,
                                            interpret=True)
    epid, ehist = partition_hist_fused_ref(keys, shift=shift, bits=bits)
    assert (np.asarray(pid) == np.asarray(epid)).all()
    assert (np.asarray(hist) == np.asarray(ehist)).all()


@pytest.mark.parametrize("n,bits", [(1024, 4), (4096, 5), (3000, 6),
                                    (5125, 2)])
def test_fused_pass_interpret_matches_jnp_path(n, bits, rng):
    """Whole fused pass: Pallas (interpret) vs the fused jnp path; sizes
    off the 1024 tile are padded and the padding taken back out."""
    from repro.core import Relation
    from repro.kernels.partition_hist.ops import fused_partition_pass
    rel = Relation(jnp.arange(n, dtype=jnp.int32),
                   jnp.asarray(rng.integers(0, n, n, dtype=np.int32)))
    got, gs, gc = fused_partition_pass(rel, shift=0, bits=bits,
                                       interpret=True)
    exp, es, ec = fused_partition_pass(rel, shift=0, bits=bits,
                                       use_pallas=False)
    assert (np.asarray(got.rid) == np.asarray(exp.rid)).all()
    assert (np.asarray(got.key) == np.asarray(exp.key)).all()
    assert (np.asarray(gs) == np.asarray(es)).all()
    assert (np.asarray(gc) == np.asarray(ec)).all()


@pytest.mark.parametrize("nb,np_,bits", [(512, 1024, 2), (2048, 4096, 3)])
def test_probe_kernel(nb, np_, bits):
    from repro.kernels.probe.ops import build_partitioned_table
    from repro.kernels.probe.probe import probe_pallas
    from repro.kernels.probe.ref import probe_ref
    b = unique_relation(nb, seed=nb)
    p = uniform_relation(np_, key_range=nb * 2, seed=np_)
    tk, tr, qk, _ = build_partitioned_table(b, p, total_bits=bits)
    got = probe_pallas(tk, tr, qk, interpret=True)
    exp = probe_ref(tk, tr, qk)
    assert (np.asarray(got) == np.asarray(exp)).all()


@pytest.mark.parametrize(
    "b,sq,sk,h,kv,d,causal,dtype",
    [(2, 256, 256, 4, 2, 64, True, jnp.float32),
     (1, 128, 384, 8, 8, 128, False, jnp.float32),
     (2, 256, 256, 4, 4, 32, True, jnp.float32),
     (1, 256, 256, 8, 2, 64, True, jnp.bfloat16)])
def test_flash_attention_kernel(b, sq, sk, h, kv, d, causal, dtype, rng):
    from repro.kernels.flash_attn.flash_attn import flash_attention_pallas
    from repro.kernels.flash_attn.ref import flash_attention_ref
    q = jnp.asarray(rng.standard_normal((b, sq, h, d)), dtype)
    k = jnp.asarray(rng.standard_normal((b, sk, kv, d)), dtype)
    v = jnp.asarray(rng.standard_normal((b, sk, kv, d)), dtype)
    got = flash_attention_pallas(q, k, v, num_kv_heads=kv, causal=causal,
                                 interpret=True)
    exp = flash_attention_ref(q, k, v, num_kv_heads=kv, causal=causal)
    tol = 3e-5 if dtype == jnp.float32 else 2e-2
    assert_allclose(np.asarray(got, np.float32), np.asarray(exp, np.float32),
                    rtol=tol, atol=tol)


@pytest.mark.parametrize("bs,nc,q,h,p,n,dtype",
                         [(2, 3, 64, 4, 32, 16, jnp.float32),
                          (1, 2, 128, 8, 64, 64, jnp.float32),
                          (1, 2, 128, 4, 64, 128, jnp.bfloat16)])
def test_ssd_kernel(bs, nc, q, h, p, n, dtype, rng):
    from repro.kernels.ssd.ref import ssd_intra_chunk_ref
    from repro.kernels.ssd.ssd import ssd_intra_chunk_pallas
    x = jnp.asarray(rng.standard_normal((bs, nc, q, h, p)), dtype)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, (bs, nc, q, h)), jnp.float32)
    bb = jnp.asarray(rng.standard_normal((bs, nc, q, n)), dtype)
    cc = jnp.asarray(rng.standard_normal((bs, nc, q, n)), dtype)
    a = jnp.asarray(-np.exp(rng.standard_normal(h) * 0.3), jnp.float32)
    got = ssd_intra_chunk_pallas(x, dt, bb, cc, a, interpret=True)
    exp = ssd_intra_chunk_ref(x, dt, bb, cc, a)
    tol = 2e-4 if dtype == jnp.float32 else 3e-2
    assert_allclose(np.asarray(got, np.float32), np.asarray(exp, np.float32),
                    rtol=tol, atol=tol)


def test_kernel_end_to_end_join_with_pallas_probe():
    """Partition with the paper's pipeline, probe with the Pallas kernel,
    and match the full-join oracle on the unique-match subset."""
    from repro.core import join_oracle
    from repro.kernels.probe.ops import build_partitioned_table, probe
    b = unique_relation(4096, seed=42)
    p = uniform_relation(8192, key_range=6000, seed=43)
    tk, tr, qk, qr = build_partitioned_table(b, p, total_bits=4)
    rid = probe(tk, tr, qk, interpret=True)
    got = np.stack([np.asarray(qr).ravel(), np.asarray(rid).ravel()], 1)
    got = got[got[:, 1] >= 0]
    got = got[np.lexsort((got[:, 1], got[:, 0]))]
    exp = join_oracle(b, p)
    assert (got == exp).all()
