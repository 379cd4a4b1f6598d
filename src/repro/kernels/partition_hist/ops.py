"""Dispatcher for the radix-partition kernels.

``fused_partition_pass`` is the data path behind one radix pass everywhere
(``repro.core.partition`` routes through it): one pid computation feeding
histogram, scan and a stable reorder.  On TPU-shaped inputs the pid and
histogram come from the fused n1+n2 Pallas kernel; elsewhere from the
equivalent jnp oracle.  n3 is the stable ``argsort`` on pid at every size.
"""
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .fused import partition_hist_fused_pallas
from .ref import partition_hist_fused_ref

# Size gate of the fused kernel (not yet set from a chip measurement);
# the jnp path runs above it.
_FUSED_MAX_TUPLES = 1 << 17
# The one-hot histogram costs 2**bits compares per tuple; wider digits
# take the jnp path.
_FUSED_MAX_BITS = 8


def fused_partition_pass(rel, *, shift: int, bits: int,
                         use_pallas: bool | None = None,
                         interpret: bool = False, mesh=None):
    """One full radix pass (n1+n2 fused, then n3).

    Returns ``(reordered Relation, starts, counts)`` for the ``bits``-wide
    digit at ``shift``; the reorder is a stable clustering by that digit.
    ``mesh``: the device mesh (axis ``"i"``) the relation is sharded over
    inside the calling program; the kernel then runs per shard under
    ``shard_map`` and the shard histograms are summed.
    """
    from repro.core.relation import Relation

    n = rel.key.shape[0]
    shards = 1 if mesh is None else mesh.size
    if use_pallas is None:
        use_pallas = (jax.default_backend() == "tpu"
                      and n <= _FUSED_MAX_TUPLES and bits <= _FUSED_MAX_BITS)
    if use_pallas or interpret:
        def pid_hist(keys):
            pid, hist = partition_hist_fused_pallas(
                keys, shift=shift, bits=bits, interpret=interpret)
            return pid, (hist if mesh is None else jax.lax.psum(hist, "i"))

        if mesh is not None:
            pid_hist = jax.shard_map(pid_hist, mesh=mesh, in_specs=P("i"),
                                     out_specs=(P("i"), P()),
                                     check_vma=False)
        # Whole (8, 128) tiles per shard: zero keys pad the tail, and
        # their pids and counts are taken back out.
        pad = -n % (1024 * shards)
        pid, counts = pid_hist(jnp.pad(rel.key, (0, pad)))
        if pad:
            pid = pid[:n]
            counts = counts - partition_hist_fused_ref(
                jnp.zeros((pad,), rel.key.dtype), shift=shift, bits=bits)[1]
    else:
        pid, counts = partition_hist_fused_ref(rel.key, shift=shift,
                                               bits=bits)
    starts = jnp.cumsum(counts) - counts
    order = jnp.argsort(pid, stable=True)
    return Relation(rel.rid[order], rel.key[order]), starts, counts
