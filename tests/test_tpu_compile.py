"""Ahead-of-time compiles of the main-path Pallas kernels for TPU v5e.

Interpret mode checks a kernel's arithmetic, not whether Mosaic accepts it.
These tests compile each kernel that the join path dispatches to, at real
widths, for a described (not attached) ``v5e:2x2`` topology, and require
the Mosaic custom call in the compiled program.  Dispatchers see the CPU
backend here, so each call forces ``use_pallas=True``.
"""
import os
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.core import Relation
from repro.kernels.agg.ops import segmented_aggregate
from repro.kernels.partition_hist.ops import fused_partition_pass


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def g_group(topo):
    """The G-group mesh of a four-chip host (C = chip 0, G = chips 1-3)."""
    return Mesh(topo.devices[1:4], ("i",))


def _i32(n, sharding):
    return jax.ShapeDtypeStruct((n,), jnp.int32, sharding=sharding)


def _assert_kernel(lowered):
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text


def test_fused_partition_pass_compiles(one_chip):
    n = 1 << 17
    rel = Relation(_i32(n, one_chip), _i32(n, one_chip))
    _assert_kernel(jax.jit(partial(fused_partition_pass, shift=0, bits=8,
                                   use_pallas=True)).lower(rel))


@pytest.mark.parametrize("wrap32", [False, True])
@pytest.mark.parametrize("slots", [1024, 1 << 14])
def test_segmented_aggregate_compiles(slots, wrap32, one_chip):
    n = 1 << 20
    _assert_kernel(jax.jit(partial(
        segmented_aggregate, num_slots=slots, wrap32=wrap32,
        use_pallas=True)).lower(_i32(n, one_chip), _i32(n, one_chip)))


@pytest.mark.parametrize("n", [3 * 40960, 3 * 33333])
def test_fused_partition_pass_compiles_sharded(n, g_group):
    """Inside a program sharded over the G group: per-shard kernel under
    shard_map (the second size pads each shard to whole tiles)."""
    sh = NamedSharding(g_group, P("i"))
    rel = Relation(_i32(n, sh), _i32(n, sh))
    _assert_kernel(jax.jit(partial(fused_partition_pass, shift=0, bits=6,
                                   use_pallas=True, mesh=g_group)).lower(rel))


def test_segmented_aggregate_compiles_sharded(g_group):
    sh = NamedSharding(g_group, P("i"))
    n = 3 * 5120
    _assert_kernel(jax.jit(partial(
        segmented_aggregate, num_slots=1 << 14, use_pallas=True,
        mesh=g_group)).lower(_i32(n, sh), _i32(n, sh)))


@pytest.mark.parametrize("kernel", ["partition_hist_fused",
                                    "segmented_aggregate"])
def test_kernel_keeps_its_stable_name(kernel, one_chip):
    """The ``name`` each main-path ``pallas_call`` passes reaches the
    lowered module (Mosaic's ``kernel_name``) and names the compiled
    custom call, whose HLO instruction name is the op name a device
    trace shows."""
    import re
    from repro.kernels.agg import agg
    from repro.kernels.partition_hist import fused
    if kernel == "partition_hist_fused":
        assert fused.KERNEL_NAME == kernel
        n = 1 << 17
        lowered = jax.jit(partial(fused_partition_pass, shift=0, bits=8,
                                  use_pallas=True)).lower(
            Relation(_i32(n, one_chip), _i32(n, one_chip)))
    else:
        assert agg.KERNEL_NAME == kernel
        n = 1 << 20
        lowered = jax.jit(partial(segmented_aggregate, num_slots=1024,
                                  use_pallas=True)).lower(
            _i32(n, one_chip), _i32(n, one_chip))
    assert f'kernel_name = "{kernel}"' in lowered.as_text()
    assert re.search(rf"%{kernel}(\.\d+)? = .*custom-call",
                     lowered.compile().as_text())
