"""Pallas TPU kernel: segmented aggregation (group-by's a2 step).

After the fused radix passes cluster group keys and a stable sort assigns
each tuple a dense group slot id, the remaining work is one streaming
reduction: per slot, accumulate count / sum / min / max of the value
column.  This kernel does all four in a single VMEM pass over the tuples —
the aggregation analogue of the fused n1+n2 histogram kernel: every grid
step adds its tile's one-hot contributions into the shared per-slot output
blocks (same output block for every step -> sequential accumulation, the
TPU-idiomatic replacement for atomic aggregation buckets).

Tuples with ``gid == -1`` (pad sentinels) match no slot and contribute
nothing.  The one-hot expansion is O(tile * num_slots) per tile, so this
kernel targets the VMEM-resident per-partition working sets the planner
produces; ``ops.py`` gates dispatch by slot count and runs the masked
``jax.ops.segment_*`` path otherwise.

Sum width: by default sums accumulate *wide* — exact int64 semantics
carried as several int32 channels, since the TPU VPU (and jax with x64
disabled) has no native int64.  The value column is reinterpreted as
uint32 and split into fixed-width bit chunks; each chunk's per-slot sum
must fit int32, so the chunk width adapts to the (static) input size —
8-bit chunks to ~8.4M tuples per call, 6-bit to ~34M, 4-bit to ~143M
(``wide_chunk_bits``) — and the signed total is recovered as
``sum_k chunk_k * 2**(bits*k) - negatives * 2**32``
(``wide_sums_to_int64``, which infers the width from the channel
count).  ``wrap32=True`` keeps the single wrapping-int32 accumulator —
the legacy device semantics, still used by oracle-parity tests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

# Plain Python ints: jnp scalars would be captured as traced constants
# inside the Pallas kernel body, which pallas_call rejects.
INT32_MAX = 2**31 - 1
INT32_MIN = -(2**31)

# Wide sums: a b-bit chunk's per-slot sum stays exact while
# (2**b - 1) * tuples_per_slot < 2**31; narrower chunks trade more
# channels for more headroom.  The per-call row count bounds any slot.
WIDE_SUM_MAX_ROWS = (2**31 - 1) // 255        # 8-bit chunks


def wide_chunk_bits(n: int) -> int:
    """Chunk width whose per-slot sums cannot overflow at ``n`` rows."""
    for bits in (8, 6, 4):
        if n <= (2**31 - 1) // ((1 << bits) - 1):
            return bits
    raise ValueError(
        f"wide segmented sums support up to {(2**31 - 1) // 15} tuples "
        f"per call (got {n}); split the input or pass wrap32=True")


def _num_chunks(bits: int) -> int:
    return -(-32 // bits)


def wide_sums_to_int64(sm: np.ndarray) -> np.ndarray:
    """Fold the (chunks+1, slots) wide-sum channels into exact int64 sums.

    Leading channels are per-slot sums of the value's uint32 bit chunks
    (width inferred from the channel count), the last channel counts
    negative values (each negative's uint32 image is its value + 2**32,
    so the signed total subtracts that bias back out).
    """
    sm = np.asarray(sm).astype(np.int64)
    chunks = sm.shape[0] - 1
    bits = {4: 8, 6: 6, 8: 4}[chunks]
    total = np.zeros(sm.shape[1], np.int64)
    for k in range(chunks):
        total += sm[k] << (bits * k)
    return total - (sm[chunks] << 32)


def _seg_agg_kernel(gid_ref, val_ref, cnt_ref, sum_ref, mn_ref, mx_ref, *,
                    wrap32: bool, chunk_bits: int, block_rows: int):
    j = pl.program_id(0)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        cnt_ref[...] = jnp.zeros_like(cnt_ref)
        sum_ref[...] = jnp.zeros_like(sum_ref)
        mn_ref[...] = jnp.full_like(mn_ref, INT32_MAX)
        mx_ref[...] = jnp.full_like(mx_ref, INT32_MIN)

    # Slots of this block on sublanes, tuples on lanes: each (1, 128) row
    # of the tile broadcasts against it, so the tile is never flattened.
    slot_block = cnt_ref.shape[0]
    slot = (jax.lax.broadcasted_iota(jnp.int32, cnt_ref.shape, 0)
            + j * slot_block)
    gid = gid_ref[...]
    val = val_ref[...]
    if wrap32:
        chans = [val]
    else:
        u = val.astype(jnp.uint32)
        chans = [((u >> jnp.uint32(chunk_bits * k))
                  & jnp.uint32((1 << chunk_bits) - 1)).astype(jnp.int32)
                 for k in range(_num_chunks(chunk_bits))]
        chans.append((val < 0).astype(jnp.int32))
    cnt = cnt_ref[...]
    sums = [sum_ref[c] for c in range(len(chans))]
    mn = mn_ref[...]
    mx = mx_ref[...]
    for r in range(block_rows):
        hit = gid[r:r + 1, :] == slot                      # (slots, 128)
        v = val[r:r + 1, :]
        cnt += hit.astype(jnp.int32)
        for c, ch in enumerate(chans):
            sums[c] += jnp.where(hit, ch[r:r + 1, :], 0)
        mn = jnp.minimum(mn, jnp.where(hit, v, INT32_MAX))
        mx = jnp.maximum(mx, jnp.where(hit, v, INT32_MIN))
    cnt_ref[...] = cnt
    for c in range(len(chans)):
        sum_ref[c] = sums[c]
    mn_ref[...] = mn
    mx_ref[...] = mx


# Stable name of the kernel in lowered modules and device traces.
KERNEL_NAME = "segmented_aggregate"

# Slots per grid block: one block's per-lane accumulators (count, min,
# max and up to nine sum channels, 128 KiB each) stay well inside VMEM.
SLOT_BLOCK = 256


@functools.partial(jax.jit,
                   static_argnames=("num_slots", "block_rows", "interpret",
                                    "wrap32", "chunk_bits"))
def seg_agg_pallas(gid: jax.Array, val: jax.Array, *, num_slots: int,
                   block_rows: int = 8, interpret: bool = False,
                   wrap32: bool = False, chunk_bits: int | None = None):
    """gid/val: (n,) int32, n % (block_rows*128) == 0; gid in [-1, num_slots).

    Returns ``(count, sum, min, max)``: count/min/max are ``(num_slots,)``
    int32; sum is ``(chunks+1, num_slots)`` wide channels by default
    (chunk width adapted to ``n`` unless ``chunk_bits`` is given; decode
    with ``wide_sums_to_int64``) or ``(num_slots,)`` wrapping int32 under
    ``wrap32=True``.  Empty slots report count 0, sum 0, min INT32_MAX,
    max INT32_MIN (neutral elements).

    Grid: slot blocks (outer) x tuple tiles (inner).  Every accumulator is
    kept per lane, ``(slots, 128)``, and folded over its lanes after the
    kernel.
    """
    n = gid.shape[0]
    lanes = 128
    rows = n // lanes
    assert rows % block_rows == 0 and n == rows * lanes, (n, block_rows)
    if chunk_bits is None:
        chunk_bits = wide_chunk_bits(n)
    sum_rows = 1 if wrap32 else _num_chunks(chunk_bits) + 1
    sb = min(SLOT_BLOCK, -(-num_slots // 8) * 8)
    slots = -(-num_slots // sb) * sb
    acc = jax.ShapeDtypeStruct((slots, lanes), jnp.int32)
    acc_spec = pl.BlockSpec((sb, lanes), lambda j, i: (j, 0))
    tile_spec = pl.BlockSpec((block_rows, lanes), lambda j, i: (i, 0))
    cnt, sm, mn, mx = pl.pallas_call(
        functools.partial(_seg_agg_kernel, wrap32=wrap32,
                          chunk_bits=chunk_bits, block_rows=block_rows),
        grid=(slots // sb, rows // block_rows),
        in_specs=[tile_spec, tile_spec],
        out_specs=[acc_spec,
                   pl.BlockSpec((sum_rows, sb, lanes), lambda j, i: (0, j, 0)),
                   acc_spec, acc_spec],
        out_shape=[acc,
                   jax.ShapeDtypeStruct((sum_rows, slots, lanes), jnp.int32),
                   acc, acc],
        interpret=interpret,
        name=KERNEL_NAME,
    )(gid.reshape(rows, lanes), val.reshape(rows, lanes))
    sm = sm.sum(axis=-1)[:, :num_slots]
    return (cnt.sum(axis=-1)[:num_slots], sm[0] if wrap32 else sm,
            mn.min(axis=-1)[:num_slots], mx.max(axis=-1)[:num_slots])
