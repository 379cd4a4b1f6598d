"""Plain NumPy reference of the equi-join ``phj_paper_16m`` runs.

A straightforward sort-merge join, written independently of the program:
every (probe_rid, build_rid) pair whose keys are equal.  A pair is
encoded as one int64 ``probe_rid << 32 | build_rid`` so that two answers
compare as sorted vectors.
"""
from __future__ import annotations

import numpy as np


def encode(probe_rid: np.ndarray, build_rid: np.ndarray) -> np.ndarray:
    return (np.asarray(probe_rid).astype(np.int64) << 32) | \
        np.asarray(build_rid).astype(np.int64)


def join_codes(build_key, build_rid, probe_key, probe_rid) -> np.ndarray:
    """Sorted pair codes of the equi-join ``build.key == probe.key``."""
    order = np.argsort(build_key, kind="stable")
    bk, br = np.asarray(build_key)[order], np.asarray(build_rid)[order]
    pk = np.asarray(probe_key)
    lo = np.searchsorted(bk, pk, side="left")
    hi = np.searchsorted(bk, pk, side="right")
    counts = hi - lo
    probe_of = np.repeat(np.arange(pk.shape[0]), counts)
    starts = np.cumsum(counts) - counts
    within = np.arange(probe_of.shape[0]) - starts[probe_of]
    codes = encode(np.asarray(probe_rid)[probe_of], br[lo[probe_of] + within])
    codes.sort()
    return codes


def compare(got: np.ndarray, ref: np.ndarray) -> tuple[int, int]:
    """(pairs missing from ``got``, pairs in ``got`` not in ``ref``), with
    multiplicity; both are sorted code vectors."""
    if got.shape == ref.shape and np.array_equal(got, ref):
        return 0, 0
    gu, gc = np.unique(got, return_counts=True)
    ru, rc = np.unique(ref, return_counts=True)
    both, gi, ri = np.intersect1d(gu, ru, assume_unique=True,
                                  return_indices=True)
    common = np.minimum(gc[gi], rc[ri]).sum()
    return int(ref.shape[0] - common), int(got.shape[0] - common)


def control_codes(build_key, build_rid, probe_key, probe_rid,
                  key_bits: int) -> np.ndarray:
    """The control: the same join with the key compare cut to its low
    ``key_bits`` bits (a hash match taken for a key match), which breaks
    the exact-answer guarantee."""
    mask = (1 << key_bits) - 1
    return join_codes(np.asarray(build_key) & mask, build_rid,
                      np.asarray(probe_key) & mask, probe_rid)
