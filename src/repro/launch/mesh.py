"""Production mesh construction (TPU v5e pods).

Single pod: (16, 16) = 256 chips, axes ("data", "model").
Multi-pod:  (2, 16, 16) = 512 chips, axes ("pod", "data", "model") — the
"pod" axis crosses DCN, the paper's discrete-architecture regime, so only
coarse-grained (DP / compressed-gradient) communication is mapped to it.

A function, not a module constant: importing this module never touches jax
device state (the dry-run sets XLA_FLAGS before any jax initialization).
"""
from __future__ import annotations

import jax


def make_auto_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis of type Auto."""
    kw = {"devices": devices} if devices is not None else {}
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         **kw)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_auto_mesh(shape, axes)


def make_host_mesh(model: int | None = None):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    model = model or 1
    data = n // model
    return make_auto_mesh((data, model), ("data", "model"))


# v5e hardware constants (per chip) — used by roofline + cost model.
HW = {
    "peak_bf16_flops": 197e12,
    "hbm_bw": 819e9,
    "ici_link_bw": 50e9,          # per link
    "dcn_bw": 3.2e9,              # per host, pod-to-pod
    "hbm_bytes": 16 * 1024 ** 3,
}
