"""Faults planted under the timed path, and the control put in its place.

Each is a context manager that swaps the program's entry point, as the
benchmark's drivers look it up, for a broken one: the harness must then
report ``correct`` false.  Used by the tests on the CPU and by
``bench/control.py`` on the chip.
"""

from __future__ import annotations

import contextlib
from types import SimpleNamespace

import jax
import jax.numpy as jnp

COUNTER_FAULTS = ("state_unchanged", "half_batch", "answer_altered",
                  "exchange_left_out")
BFS_FAULTS = ("state_unchanged", "half_batch", "answer_altered")


@contextlib.contextmanager
def swapped(obj, attr: str, new):
    old = getattr(obj, attr)
    setattr(obj, attr, new)
    jax.clear_caches()           # programs traced with the old entry go
    try:
        yield
    finally:
        setattr(obj, attr, old)
        jax.clear_caches()


def _result(table_data, fetched, like):
    return SimpleNamespace(table=like.table.with_data(table_data)
                           if hasattr(like.table, "with_data")
                           else SimpleNamespace(data=table_data),
                           fetched=fetched, success=like.success)


def counter_fault(kind: str):
    """A broken `repro.atomics.execute` for the counter drivers."""
    from repro import atomics
    real = atomics.execute

    def broken(table, op, **kw):
        if kind == "state_unchanged":
            res = real(table, op, **kw)
            data = table.data if hasattr(table, "data") else table
            return _result(data, res.fetched, res)
        if kind == "half_batch":
            n = op.indices.shape[0]
            keep = jnp.arange(n) < n // 2
            idx = jnp.where(keep, op.indices, -1)       # out of range: dropped
            res = real(table, type(op)(idx, op.values), **kw)
            return _result(res.table.data,
                           jnp.where(keep, res.fetched, 0), res)
        if kind == "answer_altered":
            res = real(table, op, **kw)
            return _result(res.table.data, res.fetched.at[0].add(1), res)
        if kind == "exchange_left_out":
            return _local_only(real, table, op, **kw)
        raise ValueError(kind)
    return swapped(atomics, "execute", broken)


def _local_only(real, table, op, **kw):
    """Each shard applies the ops it owns and drops the rest: the exchange
    between chips left out."""
    from repro import atomics
    if not getattr(table, "is_sharded", False):
        return real(table, op, **kw)
    m_local = table.data.shape[0]
    axes = table.axis if isinstance(table.axis, tuple) else (table.axis,)
    rank = 0
    for a in axes:
        rank = rank * jax.lax.axis_size(a) + jax.lax.axis_index(a)
    base = rank * m_local
    local = (op.indices >= base) & (op.indices < base + m_local)
    idx = jnp.where(local, op.indices - base, -1)
    kw = {k: v for k, v in kw.items() if k not in ("strategy",
                                                    "distinct_slots")}
    res = real(atomics.AtomicTable(table.data), type(op)(idx, op.values),
               **kw)
    return _result(res.table.data, jnp.where(local, res.fetched, 0),
                   SimpleNamespace(table=table, success=res.success))


def counter_control():
    """`faa_reverse_arrival` in the place of `repro.atomics.execute`."""
    from repro import atomics
    from bench.reference.controls import faa_reverse_arrival

    def control(table, op, **kw):
        data = table.data if hasattr(table, "data") else table
        new, fetched = faa_reverse_arrival(data, op.indices, op.values)
        return SimpleNamespace(table=SimpleNamespace(data=new),
                               fetched=fetched, success=None)
    return swapped(atomics, "execute", control)


def sharded_control():
    """The program's own reverse-rank path in the place of the forward one:
    the chips' batches serialized in descending device rank."""
    from repro import atomics
    real = atomics.execute

    def control(table, op, **kw):
        return real(table, op, **dict(kw, reverse_ranks=True))
    return swapped(atomics, "execute", control)


CONTROLS = {"counters_eager": counter_control,
            "counters_sharded": sharded_control}


def bfs_fault(kind: str):
    """A broken `repro.core.bfs.bfs` for the BFS driver."""
    from repro.core import bfs as bfs_mod
    real = bfs_mod.bfs

    def broken(src, dst, n, root=0, **kw):
        if kind == "half_batch":
            half = src.shape[0] // 2
            return real(src[:half], dst[:half], n, root=root, **kw)
        res = real(src, dst, n, root=root, **kw)
        if kind == "state_unchanged":
            parent = jnp.full((n,), -1, jnp.int32).at[root].set(root)
        elif kind == "answer_altered":
            moved = jnp.argmax(res.parent != jnp.arange(n))
            parent = res.parent.at[moved].set(root)
        else:
            raise ValueError(kind)
        return type(res)(parent=parent, levels=res.levels,
                         edges_traversed=res.edges_traversed)
    return swapped(bfs_mod, "bfs", broken)


def bfs_control():
    from repro.core import bfs as bfs_mod
    from bench.reference.controls import bfs_last_arrival
    return swapped(bfs_mod, "bfs", bfs_last_arrival)


CONTROLS["bfs"] = bfs_control


def planted(driver: str, mode: str):
    """``control``, or ``fault:<kind>``, for a cell of ``driver``."""
    if mode == "control":
        return CONTROLS[driver]()
    kind = mode.split(":", 1)[1]
    return bfs_fault(kind) if driver == "bfs" else counter_fault(kind)
