"""Pure-jnp oracles for the radix-partition kernels."""
import jax
import jax.numpy as jnp


def radix_hist_ref(pid, *, num_parts: int):
    return jax.ops.segment_sum(jnp.ones_like(pid), pid,
                               num_segments=num_parts).astype(jnp.int32)


def partition_hist_fused_ref(keys, *, shift: int, bits: int):
    """Oracle for the fused n1+n2 kernel: (pid, hist)."""
    from repro.core.relation import radix_of
    pid = radix_of(keys, shift=shift, bits=bits)
    return pid, radix_hist_ref(pid, num_parts=1 << bits)
