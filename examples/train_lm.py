"""End-to-end LM training: ~100M-parameter dense model, few hundred steps.

    PYTHONPATH=src python examples/train_lm.py --steps 300

One CPU core sustains ~100M params at seq 128 / batch 4; on a pod the same
script scales through repro.launch.train (this example is the minimal
self-contained form: config -> data -> sharded train step -> checkpoints).
Use --tiny for a seconds-long demo run.
"""
import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.checkpoint import CheckpointManager
from repro.configs import ShapeSpec, get_config
from repro.data.pipeline import SyntheticLM
from repro.distributed.sharding import TRAIN_RULES
from repro.launch.mesh import make_host_mesh
from repro.models import transformer as tfm
from repro.optim.adamw import AdamWConfig, adamw_init
from repro.train.step import make_train_step


def config_100m():
    """qwen3-family block at ~100M params."""
    return dataclasses.replace(
        get_config("qwen3_8b"), name="qwen3_100m", num_layers=10,
        d_model=640, num_heads=10, num_kv_heads=2, head_dim=64, d_ff=1792,
        vocab_size=32_000, rope_theta=1e4, remat="none")


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    args = ap.parse_args()

    cfg = config_100m()
    seq, batch = 128, 4
    if args.tiny:
        cfg = dataclasses.replace(cfg, num_layers=2, d_model=128,
                                  num_heads=4, num_kv_heads=2, head_dim=32,
                                  d_ff=256, vocab_size=2048)
        seq, batch = 64, 4
    mesh = make_host_mesh()
    opt = AdamWConfig(lr=6e-4, warmup_steps=20, total_steps=args.steps)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    n = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    print(f"{cfg.name}: {n/1e6:.1f}M params, {args.steps} steps of "
          f"{batch}x{seq} tokens")
    opt_state = adamw_init(params, opt)
    step_fn = jax.jit(make_train_step(cfg, mesh, TRAIN_RULES, opt))
    ds = SyntheticLM(cfg.vocab_size, seq, batch)
    mgr = CheckpointManager(args.ckpt_dir, save_every=100)

    t0, first_loss = time.time(), None
    for step in range(args.steps):
        batch_np = ds.batch(step)
        params, opt_state, m = step_fn(
            params, opt_state,
            {k: jnp.asarray(v) for k, v in batch_np.items()})
        if step % 20 == 0 or step == args.steps - 1:
            loss = float(m["loss"])
            first_loss = first_loss if first_loss is not None else loss
            tok_s = (step + 1) * batch * seq / (time.time() - t0)
            print(f"  step {step:4d} loss={loss:.4f} tok/s={tok_s:,.0f}")
        mgr.maybe_save(step + 1, {"params": params, "opt": opt_state})
    print(f"loss {first_loss:.3f} -> {float(m['loss']):.3f} "
          f"(checkpoints in {args.ckpt_dir})")


if __name__ == "__main__":
    main()
