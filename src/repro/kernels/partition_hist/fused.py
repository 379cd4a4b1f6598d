"""Pallas TPU kernel: FUSED radix-partition steps n1+n2.

The seed pipeline materialized the partition-id vector between n1 (compute
partition number) and n2 (histogram): one full HBM round trip of 4 bytes per
tuple.  This kernel computes the murmur3 radix digit AND accumulates the
histogram in the same VMEM pass — the pid tile never leaves VMEM before it
is consumed (the data-path-fusion argument of Ozawa et al.).

Grid tiles stream the key vector as ``(block_rows, 128)`` tiles; each tile
writes its pid block and adds its one-hot counts into the shared
``(num_parts, 128)`` per-lane histogram block (same output block for every
grid step -> sequential accumulation, the TPU-idiomatic replacement for the
paper's atomic counters).  The tile is never flattened: each sublane row of
pids is compared against a partition iota laid out as ``(num_parts, 128)``
— partitions on sublanes, tuples on lanes — so every operation stays in the
native (8, 128) vector layout.  The 128 per-lane partial counts are summed
outside the kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Single source of truth for the hash: the same constants radix_of and the
# ref oracle use (the mix steps are written out because nested jit does not
# lower inside a compiled Pallas body).
from repro.core.relation import MURMUR_C1, MURMUR_C2

LANES = 128


def _fused_kernel(keys_ref, pid_ref, hist_ref, *, shift: int, bits: int,
                  block_rows: int):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        hist_ref[...] = jnp.zeros_like(hist_ref)

    # n1: murmur3 fmix32 + radix digit, entirely in VMEM registers.
    h = keys_ref[...].astype(jnp.uint32)
    h = h ^ (h >> 16)
    h = h * MURMUR_C1
    h = h ^ (h >> 13)
    h = h * MURMUR_C2
    h = h ^ (h >> 16)
    pid = ((h >> jnp.uint32(shift))
           & jnp.uint32((1 << bits) - 1)).astype(jnp.int32)
    pid_ref[...] = pid

    # n2: histogram of the SAME tile, before pid ever reaches HBM — one
    # sublane row (128 tuples) at a time against the partition iota.
    part = jax.lax.broadcasted_iota(jnp.int32, hist_ref.shape, 0)
    acc = hist_ref[...]
    for r in range(block_rows):
        acc += (pid[r:r + 1, :] == part).astype(jnp.int32)
    hist_ref[...] = acc


# Stable name of the kernel in lowered modules and device traces.
KERNEL_NAME = "partition_hist_fused"


@functools.partial(jax.jit,
                   static_argnames=("shift", "bits", "block_rows",
                                    "interpret"))
def partition_hist_fused_pallas(keys: jax.Array, *, shift: int, bits: int,
                                block_rows: int = 8,
                                interpret: bool = False):
    """keys: (n,) int32/uint32, n % (block_rows*128) == 0.

    Returns ``(pid, hist)``: the per-tuple partition ids for hash bits
    ``[shift, shift+bits)`` and the (2**bits,) partition histogram.
    """
    assert shift + bits <= 32, (shift, bits)
    n = keys.shape[0]
    rows = n // LANES
    assert rows % block_rows == 0 and n == rows * LANES, (n, block_rows)
    num_parts = 1 << bits
    pid, hist = pl.pallas_call(
        functools.partial(_fused_kernel, shift=shift, bits=bits,
                          block_rows=block_rows),
        grid=(rows // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
                   pl.BlockSpec((num_parts, LANES), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
                   jax.ShapeDtypeStruct((num_parts, LANES), jnp.int32)],
        interpret=interpret,
        name=KERNEL_NAME,
    )(keys.reshape(rows, LANES))
    return pid.reshape(n), hist.sum(axis=1)
