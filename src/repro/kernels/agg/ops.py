"""Dispatcher for the segmented-aggregation kernel.

``segmented_aggregate`` is the data path behind group-by's reduce step
(``repro.ops.groupby`` routes through it): the one-pass Pallas kernel on
TPU, the masked ``segment_*`` jnp path elsewhere.
"""
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .agg import seg_agg_pallas, wide_chunk_bits, wide_sums_to_int64
from .ref import seg_agg_ref

__all__ = ["segmented_aggregate", "wide_sums_to_int64"]

# The one-hot accumulation costs num_slots compares per tuple; beyond
# this many slots the jnp path wins (and always off-TPU).
_AGG_VMEM_SLOTS = 1 << 14


def segmented_aggregate(gid, val, *, num_slots: int,
                        use_pallas: bool | None = None,
                        interpret: bool = False, wrap32: bool = False,
                        mesh=None):
    """Per-slot (count, sum, min, max) of ``val`` grouped by ``gid``.

    ``gid == -1`` marks pad tuples (contribute nothing).  Sums are wide by
    default — a (chunks+1, num_slots) int32 chunk layout with exact int64
    semantics, chunk width adapted to the input size (to ~143M rows per
    call) and decoded by ``wide_sums_to_int64`` — or a single wrapping
    int32 vector under ``wrap32=True`` (legacy accumulator, kept for
    oracle parity).  Empty slots report (0, 0, INT32_MAX, INT32_MIN).

    ``mesh``: the device mesh (axis ``"i"``) the tuples are sharded over
    inside the calling program; the kernel then runs per shard under
    ``shard_map`` and the shard partials are combined.
    """
    n = gid.shape[0]
    # Raises early past the wide-sum cap; fixed from the unpadded size so
    # the channel layout matches the jnp path's.
    chunk_bits = 8 if wrap32 else wide_chunk_bits(n)
    if use_pallas is None:
        use_pallas = (jax.default_backend() == "tpu"
                      and num_slots <= _AGG_VMEM_SLOTS)
    if not (use_pallas or interpret):
        return seg_agg_ref(gid, val, num_slots=num_slots, wrap32=wrap32)
    shards = 1 if mesh is None else mesh.size
    # Whole (8, 128) tiles per shard; pad tuples' gid -1 matches no slot.
    pad = -n % (1024 * shards)
    gid = jnp.pad(gid, (0, pad), constant_values=-1)
    val = jnp.pad(val, (0, pad))

    def agg(g, v):
        cnt, sm, mn, mx = seg_agg_pallas(
            g, v, num_slots=num_slots, interpret=interpret, wrap32=wrap32,
            chunk_bits=chunk_bits)
        if mesh is None:
            return cnt, sm, mn, mx
        return (jax.lax.psum(cnt, "i"), jax.lax.psum(sm, "i"),
                jax.lax.pmin(mn, "i"), jax.lax.pmax(mx, "i"))

    if mesh is not None:
        agg = jax.shard_map(agg, mesh=mesh, in_specs=(P("i"), P("i")),
                            out_specs=P(), check_vma=False)
    return agg(gid, val)
