"""Fetch-and-add on a table of int32 counters, executed one op at a time in
arrival order: op ``i`` fetches the slot's value before it, then adds its
value; sums wrap modulo 2^32 as int32 arithmetic does."""

from __future__ import annotations

from typing import Dict, Sequence, Tuple

import numpy as np


def wrap32(x) -> np.ndarray:
    """int64 -> int32 modulo 2^32."""
    x = np.asarray(x, np.int64)
    return ((x + (1 << 31)) % (1 << 32) - (1 << 31)).astype(np.int32)


def faa(table: np.ndarray, idx: np.ndarray,
        vals: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """(new table, fetched) of one batch run serially in arrival order."""
    keys, sums = batch_sums(idx, vals)
    new = table.copy()
    new[keys] = wrap32(new[keys].astype(np.int64) + sums)
    return new, fetch(table, idx, vals)


def fetch(table: np.ndarray, idx: np.ndarray, vals: np.ndarray) -> np.ndarray:
    """The value each op of one batch fetches when the batch runs serially
    in arrival order: the slot's value before the batch plus the values of
    the earlier ops on the same slot."""
    order = np.argsort(idx, kind="stable")
    si = np.asarray(idx)[order]
    sv = np.asarray(vals, np.int64)[order]
    first = np.ones(si.shape, bool)
    first[1:] = si[1:] != si[:-1]
    excl = np.cumsum(sv) - sv                 # sum of all earlier ops
    head = np.maximum.accumulate(np.where(first, np.arange(si.size), 0))
    fetched = np.empty(si.shape, np.int64)
    fetched[order] = table[si].astype(np.int64) + excl - excl[head]
    return wrap32(fetched)


def batch_sums(idx, vals) -> Tuple[np.ndarray, np.ndarray]:
    """Distinct keys of a batch and the int64 sum of the values at each."""
    idx = np.asarray(idx)
    order = np.argsort(idx, kind="stable")
    si = idx[order]
    sv = np.asarray(vals, np.int64)[order]
    if si.size == 0:
        return si, sv
    starts = np.flatnonzero(np.r_[True, si[1:] != si[:-1]])
    return si[starts], np.add.reduceat(sv, starts)


def replay(table0: np.ndarray, pool_idx: Sequence[np.ndarray],
           pool_vals: Sequence[np.ndarray], batches: int,
           wanted) -> Tuple[np.ndarray, Dict[int, np.ndarray]]:
    """A window that ran batch ``k`` = pool batch ``k % P`` for
    k = 0 .. batches-1 on a table that started as ``table0``: the table after
    the last batch, and the fetched values of each batch in ``wanted``.

    Additions commute, so between two wanted batches the table moves by
    each pool batch's sums times the number of times it ran there."""
    table = np.array(table0, np.int32)
    sums = [batch_sums(i, v) for i, v in zip(pool_idx, pool_vals)]
    n_pool = len(sums)

    def ran(p, k):              # batches j < k with j % P == p
        return (k + n_pool - 1 - p) // n_pool

    fetched: Dict[int, np.ndarray] = {}
    done = 0
    for k in sorted(set(wanted) | {batches}):
        if k - done > n_pool:
            steps = [(p, ran(p, k) - ran(p, done)) for p in range(n_pool)]
        else:
            steps = [(j % n_pool, 1) for j in range(done, k)]
        for p, times in steps:
            keys, s = sums[p]
            table[keys] = wrap32(table[keys].astype(np.int64) + times * s)
        done = k
        if k < batches:
            p = k % n_pool
            fetched[k] = fetch(table, pool_idx[p], pool_vals[p])
    return table, fetched
