"""Checkpoint/restore: roundtrip, atomic commit, resume, elastic restore."""
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import (CheckpointManager, latest_step,
                              restore_checkpoint, save_checkpoint)


def _tree(seed=0):
    k = jax.random.PRNGKey(seed)
    return {"w": {"a": jax.random.normal(k, (16, 8)),
                  "b": jnp.arange(10, dtype=jnp.int32)},
            "step": jnp.int32(7)}


def test_roundtrip(tmp_path):
    t = _tree()
    save_checkpoint(str(tmp_path), 5, t)
    like = jax.tree.map(jnp.zeros_like, t)
    r = restore_checkpoint(str(tmp_path), 5, like)
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(r)):
        assert (np.asarray(a) == np.asarray(b)).all()


def test_latest_pointer_and_prune(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2, save_every=1)
    for s in (1, 2, 3, 4):
        assert mgr.maybe_save(s, _tree(s))
    assert latest_step(str(tmp_path)) == 4
    dirs = sorted(d for d in os.listdir(tmp_path) if d.startswith("step_"))
    assert dirs == ["step_00000003", "step_00000004"]


def test_crash_mid_write_ignored(tmp_path):
    save_checkpoint(str(tmp_path), 1, _tree())
    os.makedirs(tmp_path / "step_00000002.tmp")  # simulated torn write
    assert latest_step(str(tmp_path)) == 1


def test_restore_latest_resumes(tmp_path):
    mgr = CheckpointManager(str(tmp_path), save_every=1)
    t = _tree(3)
    mgr.maybe_save(3, t)
    like = jax.tree.map(jnp.zeros_like, t)
    restored, step = mgr.restore_latest(like)
    assert step == 3
    assert (np.asarray(restored["w"]["a"]) == np.asarray(t["w"]["a"])).all()


def test_elastic_restore_to_new_sharding(tmp_path):
    """Save unsharded, restore under a different (host) mesh sharding —
    the any-topology restore path (DESIGN.md §5)."""
    t = _tree()
    save_checkpoint(str(tmp_path), 9, t)
    from repro.launch.mesh import make_auto_mesh
    mesh = make_auto_mesh((1,), ("data",))
    sh = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("data"))
    shardings = {"w": {"a": sh, "b": sh},
                 "step": jax.sharding.NamedSharding(
                     mesh, jax.sharding.PartitionSpec())}
    r = restore_checkpoint(str(tmp_path), 9, t, shardings_tree=shardings)
    assert r["w"]["a"].sharding.is_equivalent_to(sh, 2)


def test_train_resume_equivalence(tmp_path):
    """Training 2 steps == training 1, checkpointing, restoring, 1 more."""
    from repro.configs import ShapeSpec, all_configs, reduced
    from repro.data.pipeline import make_batch
    from repro.distributed.sharding import TRAIN_RULES
    from repro.launch.mesh import make_host_mesh
    from repro.models import transformer as tfm
    from repro.optim.adamw import AdamWConfig, adamw_init
    from repro.train.step import make_train_step

    cfg = reduced(all_configs()["phi3_mini_3_8b"])
    shape = ShapeSpec("t", 32, 2, "train")
    opt = AdamWConfig(lr=1e-3)
    mesh = make_host_mesh()
    step = jax.jit(make_train_step(cfg, mesh, TRAIN_RULES, opt))
    p0 = tfm.init_params(cfg, jax.random.PRNGKey(0))
    s0 = adamw_init(p0, opt)

    pa, sa = p0, s0
    for i in range(2):
        pa, sa, _ = step(pa, sa, make_batch(cfg, shape, step=i))

    pb, sb = p0, s0
    pb, sb, _ = step(pb, sb, make_batch(cfg, shape, step=0))
    save_checkpoint(str(tmp_path), 1, {"params": pb, "opt": sb})
    like = {"params": jax.tree.map(jnp.zeros_like, pb),
            "opt": jax.tree.map(jnp.zeros_like, sb)}
    rest = restore_checkpoint(str(tmp_path), 1, like)
    pc, sc, _ = step(rest["params"], rest["opt"],
                     make_batch(cfg, shape, step=1))
    for a, b in zip(jax.tree.leaves(pa), jax.tree.leaves(pc)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-6)
