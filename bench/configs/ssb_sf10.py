"""Plain NumPy reference of SSB flight 2 over the tables ``ssb_sf10`` runs.

    select d_year, p_brand1, sum(lo_revenue)
    from lineorder, date, part, supplier
    where lo_orderdate = d_datekey and lo_partkey = p_partkey
      and lo_suppkey = s_suppkey and <part predicate> and <supplier predicate>
    group by d_year, p_brand1

Written independently of the program: each dimension key is looked up by
binary search over its sorted key column (dimension keys are primary keys,
which is checked), predicates are ``lo <= column < hi`` on the encoded
columns, and sums are int64.  ``wrap32=True`` computes the sums in int32
arithmetic instead: the control, one precision below the guarantee.
"""
from __future__ import annotations

import numpy as np


def _lookup(fk: np.ndarray, pk: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(found, row) of each foreign key in the primary-key column ``pk``."""
    order = np.argsort(pk, kind="stable")
    spk = pk[order]
    if spk.size > 1 and not np.all(spk[1:] != spk[:-1]):
        raise ValueError("dimension key is not unique")
    pos = np.searchsorted(spk, fk)
    pos_c = np.minimum(pos, max(spk.size - 1, 0))
    found = (pos < spk.size) & (spk[pos_c] == fk)
    return found, order[pos_c]


def _mask(table: dict, pred) -> np.ndarray:
    col, lo, hi = pred
    v = table[col]
    return (v >= lo) & (v < hi)


def lookups(tables: dict) -> dict:
    """Every lineorder row's (found, row) in part, supplier and date: the
    same for every query of the flight, so a caller may keep them."""
    lo = tables["lineorder"]
    return {"part": _lookup(lo["lo_partkey"], tables["part"]["p_partkey"]),
            "supplier": _lookup(lo["lo_suppkey"],
                                tables["supplier"]["s_suppkey"]),
            "date": _lookup(lo["lo_orderdate"], tables["date"]["d_datekey"])}


def flight2(tables: dict, template: dict, *, wrap32: bool = False,
            found: dict | None = None) -> tuple[np.ndarray, int]:
    """(rows sorted by (d_year, p_brand1): columns d_year, p_brand1, sum;
    number of joined rows before grouping).  ``found`` is ``lookups``'s
    result where the caller kept it."""
    lo, part = tables["lineorder"], tables["part"]
    sup, date = tables["supplier"], tables["date"]
    found = found or lookups(tables)
    fp, rp = found["part"]
    fs, rs = found["supplier"]
    fd, rd = found["date"]
    keep = fp.copy()
    keep[keep] &= _mask(part, template["part"])[rp[keep]]
    keep &= fs
    keep[keep] &= _mask(sup, template["supplier"])[rs[keep]]
    keep &= fd
    year = date["d_year"][rd[keep]].astype(np.int64)
    brand = part["p_brand1"][rp[keep]].astype(np.int64)
    rev = lo["lo_revenue"][keep].astype(np.int64)
    joined = int(keep.sum())
    key = year * (1 << 32) + brand
    order = np.argsort(key, kind="stable")
    key, rev = key[order], rev[order]
    starts = np.flatnonzero(np.r_[True, key[1:] != key[:-1]]) if key.size \
        else np.zeros(0, np.int64)
    if wrap32:
        sums = np.add.reduceat(rev.astype(np.int32), starts, dtype=np.int32) \
            if key.size else np.zeros(0, np.int32)
    else:
        sums = np.add.reduceat(rev, starts) if key.size \
            else np.zeros(0, np.int64)
    uk = key[starts] if key.size else key
    rows = np.stack([uk >> 32, uk & 0xFFFFFFFF, sums.astype(np.int64)],
                    axis=1)
    return rows, joined


def compare(got: np.ndarray, want: np.ndarray) -> int:
    """Rows of either side without an equal row on the other (each row is
    one group: its keys and its sum)."""
    g = {tuple(r) for r in got.tolist()}
    w = {tuple(r) for r in want.tolist()}
    dup = (got.shape[0] - len(g)) + (want.shape[0] - len(w))
    return len(g ^ w) + dup
