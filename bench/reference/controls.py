"""The references with one stated guarantee broken, in JAX, to stand in the
program's place: a comparison that cannot tell them from the program is too
weak to decide ``correct``.

* `faa_reverse_arrival`: fetch-and-add whose table is right but whose
  fetched values serialize the ops of one slot in reverse arrival order (a
  combine that drops the arrival-order guarantee, the step an unordered
  reduction would take).
* `bfs_last_arrival`: a breadth-first search that is a valid BFS tree but
  settles each vertex on the last frontier edge to reach it, not the first
  (compare-and-swap replaced by an unordered store).
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp


@jax.jit
def faa_reverse_arrival(table, idx, vals):
    """(new table, fetched) with each slot's ops fetched in reverse order."""
    n = idx.shape[0]
    rev = jnp.arange(n)[::-1]
    order = jnp.lexsort((rev, idx))          # by slot, later ops first
    si, sv = idx[order], vals[order]
    first = jnp.concatenate([jnp.array([True]), si[1:] != si[:-1]])
    excl = jnp.cumsum(sv) - sv
    head = jax.lax.cummax(jnp.where(first, jnp.arange(n), 0))
    fetched = jnp.zeros_like(vals).at[order].set(
        table[si] + excl - excl[head])
    return table.at[idx].add(vals), fetched


class ControlBfs(NamedTuple):
    parent: jax.Array
    levels: int
    edges_traversed: int


@partial(jax.jit, static_argnames=("n",))
def _bfs_last(src, dst, root, n: int):
    e = src.shape[0]
    parent = jnp.full((n,), -1, jnp.int32).at[root].set(root)
    frontier = jnp.zeros((n,), bool).at[root].set(True)

    def body(state):
        parent, frontier, lvl = state
        live = frontier[src] & (parent[dst] == -1)
        last = jax.ops.segment_max(jnp.where(live, jnp.arange(e), -1), dst,
                                   num_segments=n)
        new = last >= 0
        parent = jnp.where(new, src[jnp.maximum(last, 0)], parent)
        return parent, new, lvl + 1

    def cond(state):
        return jnp.any(state[1])

    parent, _, lvl = jax.lax.while_loop(cond, body,
                                        (parent, frontier, jnp.int32(0)))
    return parent, lvl


def bfs_last_arrival(src, dst, n: int, root: int = 0, op: str = "cas",
                     backend: str = "auto") -> ControlBfs:
    """Signature of the program's `bfs`, so it can stand in its place."""
    del op, backend
    parent, lvl = _bfs_last(jnp.asarray(src, jnp.int32),
                            jnp.asarray(dst, jnp.int32), jnp.int32(root), n)
    return ControlBfs(parent, int(lvl), 0)
