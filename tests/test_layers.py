"""Layer-level unit tests: MoE dispatch equivalence, SSD chunk invariance,
sharding rule engine, compression, data pipeline determinism."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.configs import MoECfg, SSMCfg, all_configs, reduced


def _moe_cfg(impl, top_k=2, experts=8):
    cfg = reduced(all_configs()["granite_moe_3b"])
    return dataclasses.replace(
        cfg, moe_impl=impl,
        moe=MoECfg(num_experts=experts, top_k=top_k, d_ff=32,
                   capacity_factor=4.0, group_size=1 << 20))


def test_moe_dense_equals_sorted():
    """The GSPMD one-hot dispatch and the paper's radix-partition dispatch
    compute the same function (capacity high enough that neither drops)."""
    from repro.layers.moe import moe_specs, moe_dense, moe_sorted
    from repro.models.params import materialize
    cfg = _moe_cfg("dense")
    params = materialize(moe_specs(cfg), jax.random.PRNGKey(0), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model),
                          jnp.float32)
    y1, a1 = moe_dense(params, cfg, x)
    y2, a2 = moe_sorted(params, cfg, x)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=2e-5)
    np.testing.assert_allclose(float(a1), float(a2), rtol=1e-5)


def test_moe_capacity_drops_consistently():
    from repro.layers.moe import moe_specs, moe_dense, moe_sorted
    from repro.models.params import materialize
    cfg = _moe_cfg("dense")
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=0.5))
    params = materialize(moe_specs(cfg), jax.random.PRNGKey(0), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, cfg.d_model))
    y1, _ = moe_dense(params, cfg, x)
    y2, _ = moe_sorted(params, cfg, x)
    # Same priority order (token-major within slot) => identical drops.
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=2e-5)


@settings(max_examples=10, deadline=None)
@given(l=st.sampled_from([32, 64, 96, 128]),
       chunk=st.sampled_from([16, 32, 64]),
       seed=st.integers(0, 1000))
def test_ssd_chunk_invariance(l, chunk, seed):
    """SSD output must not depend on the chunk length (duality check)."""
    from repro.layers.ssd import ssd_chunked
    rng = np.random.default_rng(seed)
    b, h, p, n = 1, 2, 8, 4
    x = jnp.asarray(rng.standard_normal((b, l, h, p)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.3, (b, l, h)), jnp.float32)
    a = jnp.asarray(-np.exp(rng.standard_normal(h) * 0.2), jnp.float32)
    bb = jnp.asarray(rng.standard_normal((b, l, n)), jnp.float32)
    cc = jnp.asarray(rng.standard_normal((b, l, n)), jnp.float32)
    y1, h1 = ssd_chunked(x, dt, a, bb, cc, chunk)
    y2, h2 = ssd_chunked(x, dt, a, bb, cc, l)   # single chunk
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=2e-3)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), atol=2e-3)


def test_ssd_chunked_equals_recurrence():
    """Chunked SSD == step-by-step recurrent decode (state-space duality)."""
    from repro.layers.ssd import ssd_chunked, ssd_decode_step
    rng = np.random.default_rng(3)
    b, l, h, p, n = 2, 24, 2, 4, 8
    x = jnp.asarray(rng.standard_normal((b, l, h, p)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.3, (b, l, h)), jnp.float32)
    a = jnp.asarray(-np.exp(rng.standard_normal(h) * 0.2), jnp.float32)
    bb = jnp.asarray(rng.standard_normal((b, l, n)), jnp.float32)
    cc = jnp.asarray(rng.standard_normal((b, l, n)), jnp.float32)
    y_chunk, h_final = ssd_chunked(x, dt, a, bb, cc, 8)
    hs = jnp.zeros((b, h, p, n), jnp.float32)
    ys = []
    for t in range(l):
        y, hs = ssd_decode_step(x[:, t], dt[:, t], a, bb[:, t], cc[:, t], hs)
        ys.append(y)
    y_rec = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_chunk), np.asarray(y_rec),
                               atol=2e-3)
    np.testing.assert_allclose(np.asarray(h_final), np.asarray(hs),
                               atol=2e-3)


def test_sharding_rule_engine():
    from repro.distributed.sharding import TRAIN_RULES, axes_to_spec
    from repro.launch.mesh import make_auto_mesh
    mesh = make_auto_mesh((1, 1), ("data", "model"))
    # engine falls back to None when sizes don't divide
    spec = axes_to_spec(("batch", "heads"), (3, 5), TRAIN_RULES, mesh)
    assert spec == jax.sharding.PartitionSpec(None, None) or all(
        s is None or True for s in spec)

    import numpy as _np
    devs = _np.array(jax.devices()[:1]).reshape(1, 1)
    mesh16 = jax.sharding.Mesh(devs, ("data", "model"))
    # divisibility honored: heads=40 on a 16-wide model axis -> replicated
    class FakeMesh:
        shape = {"data": 16, "model": 16}
    spec = axes_to_spec(("batch", "heads", "head_dim"), (256, 40, 128),
                        TRAIN_RULES, FakeMesh())
    assert spec[1] is None                      # 40 % 16 != 0
    spec = axes_to_spec(("batch", "heads", "head_dim"), (256, 64, 128),
                        TRAIN_RULES, FakeMesh())
    assert spec[1] == "model"
    # one mesh axis never used twice in a tensor
    spec = axes_to_spec(("vocab", "mlp"), (160, 160), TRAIN_RULES,
                        FakeMesh())
    assert not (spec[0] == "model" and spec[1] == "model")


def test_grad_compression_roundtrip(rng):
    from repro.train.compress import ef_int8_allreduce_sim
    g = {"a": jnp.asarray(rng.standard_normal((64, 64)) * 0.01,
                          jnp.float32)}
    d = ef_int8_allreduce_sim(g)
    err = np.abs(np.asarray(d["a"]) - np.asarray(g["a"])).max()
    assert err <= float(jnp.abs(g["a"]).max()) / 127 + 1e-8


def test_data_pipeline_deterministic_and_host_sharded():
    from repro.data.pipeline import SyntheticLM
    ds = SyntheticLM(vocab_size=1000, seq_len=64, global_batch=8)
    a = ds.batch(3, host_index=0, host_count=2)
    b = ds.batch(3, host_index=0, host_count=2)
    assert (a["tokens"] == b["tokens"]).all()
    c = ds.batch(3, host_index=1, host_count=2)
    assert a["tokens"].shape == (4, 64)
    assert not (a["tokens"] == c["tokens"]).all()
    # labels are next-token shifted
    assert (a["labels"][:, :-1] == a["tokens"][:, 1:]).all()
