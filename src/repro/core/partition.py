"""Radix partitioning (paper §3.1, Algorithm 2, steps n1..n3).

Each pass clusters tuples by a slice of the hash's low bits:

  n1: compute partition number        (VPU ALU map over tuples)
  n2: visit the partition header      (histogram + exclusive scan)
  n3: insert <key, rid> into partition (stable reorder = scan allocator)

On TPU there are no atomics, so n2+n3 use the deterministic
histogram -> scan -> reorder pattern (DESIGN.md §2): semantically identical
to the paper's latched partition buffers, contention-free by construction.
Multiple passes refine previous passes' clusters (paper: "performed by
multiple passes ... tuned according to the memory hierarchy"); pass ``g``
uses hash bits ``[g*bits, (g+1)*bits)`` and a globally stable reorder, so
after all passes tuples are clustered by the full ``total_bits`` radix.

This module is also the MoE dispatch engine: routing tokens to experts is a
radix partition by expert id (see ``repro.layers.moe``).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from .relation import Relation, radix_of


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Partitions:
    """A relation clustered into ``P`` partitions, with CSR headers."""

    rel: Relation            # tuples reordered so partitions are contiguous
    part_start: jax.Array    # (P,)
    part_count: jax.Array    # (P,)

    @property
    def num_partitions(self) -> int:
        return int(self.part_start.shape[0])

    def tree_flatten(self):
        return (self.rel, self.part_start, self.part_count), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def partition_n1(key: jax.Array, *, shift: int, bits: int) -> jax.Array:
    """(n1) compute partition number from the hash's bit slice."""
    return radix_of(key, shift=shift, bits=bits)


def partition_n2(pid: jax.Array, num_parts: int):
    """(n2) partition headers: histogram + exclusive scan (the allocator)."""
    counts = jax.ops.segment_sum(jnp.ones_like(pid), pid,
                                 num_segments=num_parts)
    starts = jnp.cumsum(counts) - counts
    return starts, counts


def partition_n3(rel: Relation, pid: jax.Array) -> Relation:
    """(n3) insert <key, rid> into partitions: stable reorder by pid."""
    order = jnp.argsort(pid, stable=True)
    return Relation(rel.rid[order], rel.key[order])


@partial(jax.jit, static_argnames=("schedule", "use_pallas", "interpret",
                                   "mesh"))
def radix_partition_scheduled(rel: Relation, *, schedule: tuple[int, ...],
                              use_pallas: bool | None = None,
                              interpret: bool = False,
                              mesh=None) -> Partitions:
    """Multi-pass radix partitioning over an explicit pass ``schedule``.

    ``schedule`` lists each pass's digit width, low digit first (a
    ``PassPlan.schedule`` — see ``repro.core.pass_planner``).  Every pass
    is the FUSED data path (``repro.kernels.partition_hist.ops``): n1+n2
    in one VMEM sweep, n3 as a stable sort on pid; stable reorders make
    the final layout clustered by the complete ``sum(schedule)``-bit radix.
    ``mesh`` names the device mesh ``rel`` is sharded over (see
    ``fused_partition_pass``).
    """
    from repro.kernels.partition_hist.ops import fused_partition_pass

    total_bits = sum(schedule)
    cur = rel
    shift = 0
    for bits in schedule:
        cur, _, _ = fused_partition_pass(cur, shift=shift, bits=bits,
                                         use_pallas=use_pallas,
                                         interpret=interpret, mesh=mesh)
        shift += bits
    full_pid = radix_of(cur.key, shift=0, bits=total_bits)
    start, count = partition_n2(full_pid, 1 << total_bits)
    return Partitions(cur, start, count)


def partition_pass(rel: Relation, *, shift: int, bits: int,
                   use_pallas: bool | None = None,
                   interpret: bool = False, mesh=None) -> Relation:
    """One fused partition pass (n1+n2 sweep, stable-sort n3)."""
    from repro.kernels.partition_hist.ops import fused_partition_pass

    out, _, _ = fused_partition_pass(rel, shift=shift, bits=bits,
                                     use_pallas=use_pallas,
                                     interpret=interpret, mesh=mesh)
    return out


_coop_pass_cache: dict = {}


def _jitted_pass(shift: int, bits: int, use_pallas, interpret):
    key = (shift, bits, use_pallas, interpret)
    fn = _coop_pass_cache.get(key)
    if fn is None:
        fn = _coop_pass_cache[key] = jax.jit(partial(
            partition_pass, shift=shift, bits=bits,
            use_pallas=use_pallas, interpret=interpret))
    return fn


def radix_partition_cooperative(rel: Relation, *,
                                schedule: tuple[int, ...],
                                start_pass: int = 0, check=None,
                                use_pallas: bool | None = None,
                                interpret: bool = False) -> Partitions:
    """Preemptible multi-pass partitioning: one jitted program *per pass*.

    ``radix_partition_scheduled`` compiles the whole schedule into a
    single program — nothing can stop it mid-flight.  This variant runs
    the identical fused passes but returns control to Python between
    them, calling ``check(pass_idx)`` first; a check that raises (the
    engine's ``QueryContext.check`` raising ``DeadlineExceeded``) aborts
    with ``pass_idx`` passes complete.  ``start_pass=k`` resumes a
    relation that already absorbed the schedule's first ``k`` passes (a
    checkpointed partial layout): each pass is a stable reorder on its
    own bit slice, so completed passes never need re-running.
    """
    total_bits = sum(schedule)
    cur = rel
    shift = sum(schedule[:start_pass])
    for i in range(start_pass, len(schedule)):
        if check is not None:
            check(i)
        bits = schedule[i]
        cur = _jitted_pass(shift, bits, use_pallas, interpret)(cur)
        shift += bits
    full_pid = radix_of(cur.key, shift=0, bits=total_bits)
    start, count = partition_n2(full_pid, 1 << total_bits)
    return Partitions(cur, start, count)


def radix_partition(rel: Relation, *, bits_per_pass: int,
                    num_passes: int, use_pallas: bool | None = None,
                    interpret: bool = False) -> Partitions:
    """Uniform-schedule partitioning: (n1 n2 n3) x num_passes (fused)."""
    return radix_partition_scheduled(
        rel, schedule=(bits_per_pass,) * num_passes, use_pallas=use_pallas,
        interpret=interpret)


@partial(jax.jit, static_argnames=("bits_per_pass", "num_passes"))
def radix_partition_unfused(rel: Relation, *, bits_per_pass: int,
                            num_passes: int) -> Partitions:
    """The seed's materialized 3-step path, kept as the benchmark baseline
    (``benchmarks/run.py --only partition_fused`` compares against it)."""
    total_bits = bits_per_pass * num_passes
    cur = rel
    for g in range(num_passes):
        pid = partition_n1(cur.key, shift=g * bits_per_pass,
                           bits=bits_per_pass)
        # Headers are computed every pass (n2) as in the paper; only the
        # final pass's full-radix headers are returned.
        partition_n2(pid, 1 << bits_per_pass)
        cur = partition_n3(cur, pid)
    full_pid = radix_of(cur.key, shift=0, bits=total_bits)
    start, count = partition_n2(full_pid, 1 << total_bits)
    return Partitions(cur, start, count)


def partition_ids(rel: Relation, *, total_bits: int) -> jax.Array:
    """Final partition id per tuple (for tests / divergence grouping)."""
    return radix_of(rel.key, shift=0, bits=total_bits)
