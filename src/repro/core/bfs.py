"""Graph500-style BFS with selectable RMW combiner semantics (paper §6.1).

The paper's point: CAS/SWP/FAA cost the same, so pick the primitive whose
*semantics* fit — for the bfs_tree parent array, CAS (set-if-unvisited) and
SWP (swap + revert) give simple protocols while FAA needs a revert scheme.
We reproduce the comparison with the vectorized combining RMW: per BFS
level, all frontier edges issue parent-updates through the chosen typed op
(`repro.atomics.execute`) — the cost-model auto-selected backend by default
(typically the sort-free one-hot backend for frontier-sized batches),
overridable per run for benchmarking.  The sharded variant runs the same
ops against an `AtomicTable` sharded over the mesh axis; `execute` detects
the shard_map context and routes through the exchange strategies.

Kronecker (RMAT) generator included — the paper benchmarks on Kronecker
graphs that model heavy-tailed real-world graphs.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import atomics
from repro.sharding import make_mesh, shard_map_compat

Array = jax.Array


def kronecker_graph(scale: int, edgefactor: int = 8, seed: int = 0,
                    a=0.57, b=0.19, c=0.19) -> Tuple[np.ndarray, np.ndarray]:
    """RMAT edge list (Graph500 generator), n = 2**scale nodes."""
    n_edges = edgefactor * (1 << scale)
    rng = np.random.default_rng(seed)
    src = np.zeros(n_edges, np.int64)
    dst = np.zeros(n_edges, np.int64)
    for level in range(scale):
        r = rng.random(n_edges)
        bit_src = (r >= a + b) & (r < a + b + c) | (r >= a + b + c)
        r2 = rng.random(n_edges)
        bit_dst = ((r < a + b) & (r >= a)) | (r >= a + b + c)
        del r2
        src |= bit_src.astype(np.int64) << level
        dst |= bit_dst.astype(np.int64) << level
    perm = rng.permutation(1 << scale)       # shuffle vertex labels
    return perm[src], perm[dst]


@dataclasses.dataclass
class BfsResult:
    parent: Array
    levels: int
    edges_traversed: int


@partial(jax.jit, static_argnames=("n", "op", "max_levels", "backend"))
def _bfs_run(src: Array, dst: Array, root, n: int, op: str,
             max_levels: int = 64, backend: str = "auto"):
    parent = jnp.full((n,), -1, jnp.int32).at[root].set(root)

    def level(state):
        parent, frontier, lvl, edges = state
        active = frontier[src]                       # edge's src in frontier
        cand_dst = jnp.where(active, dst, n)         # OOR -> dropped
        cand_par = src.astype(jnp.int32)
        if op == "cas":
            res = atomics.execute(
                parent, atomics.Cas(cand_dst, cand_par, expected=-1),
                backend=backend, need_fetched=False)
            new_parent = res.table.data
        elif op == "swp":
            # swap unconditionally, then revert overwrites of visited nodes.
            # The restore value is the FIRST collider's fetched (the original
            # parent), so the revert stream runs reversed (last-wins of the
            # reversed order == first in program order).
            res = atomics.execute(parent, atomics.Swp(cand_dst, cand_par),
                                  backend=backend)
            visited_before = res.fetched != -1
            revert_idx = jnp.where(visited_before, cand_dst, n)
            new_parent = atomics.execute(
                res.table, atomics.Swp(revert_idx[::-1], res.fetched[::-1]),
                backend=backend, need_fetched=False).table.data
        else:  # faa with revert (the paper's "complex scheme")
            delta = jnp.where(parent[jnp.clip(cand_dst, 0, n - 1)] == -1,
                              cand_par + 1, 0)
            res = atomics.execute(parent, atomics.Faa(cand_dst, delta),
                                  backend=backend, need_fetched=False)
            over = res.table.data  # -1 + sum(deltas); keep 1st contributor
            # revert: recompute exact winner via min-combine of parities
            first = atomics.execute(
                jnp.full((n,), jnp.iinfo(jnp.int32).max, jnp.int32),
                atomics.Min(cand_dst,
                            jnp.where(delta > 0, cand_par,
                                      jnp.iinfo(jnp.int32).max)),
                backend=backend, need_fetched=False).table.data
            new_parent = jnp.where(
                (parent == -1) & (first != jnp.iinfo(jnp.int32).max),
                first, parent)
            del over
        new_frontier = (new_parent != -1) & (parent == -1)
        edges = edges + jnp.sum(active)
        return new_parent, new_frontier, lvl + 1, edges

    def cond(state):
        _, frontier, lvl, _ = state
        return jnp.any(frontier) & (lvl < max_levels)

    frontier0 = jnp.zeros((n,), bool).at[root].set(True)
    parent, _, lvl, edges = jax.lax.while_loop(
        cond, level, (parent, frontier0, jnp.int32(0), jnp.int32(0)))
    return parent, lvl, edges


def bfs(src: np.ndarray, dst: np.ndarray, n: int, root: int = 0,
        op: str = "cas", backend: str = "auto") -> BfsResult:
    """Level-synchronous BFS; op ∈ {cas, swp, faa} picks the combiner and
    ``backend`` the RMW engine implementation ("auto" = cost-model pick)."""
    parent, lvl, edges = _bfs_run(
        jnp.asarray(src, jnp.int32), jnp.asarray(dst, jnp.int32),
        jnp.int32(root), int(n), op, backend=backend)
    return BfsResult(parent=parent, levels=int(lvl),
                     edges_traversed=int(edges))


def bfs_sharded(src: np.ndarray, dst: np.ndarray, n: int, root: int = 0,
                *, axis: str = "dev", mesh=None, strategy: str = "auto",
                op: str = "cas", max_levels: int = 64) -> BfsResult:
    """Level-synchronous BFS with the **frontier table sharded over a mesh**.

    The parent array — the paper's contended cache line — is sharded over
    `axis` (vertex ``v`` owned by shard ``v // n_local``); edges are split
    over the same devices.  Each level gathers the frontier bitmap and issues
    every frontier edge's parent update through the sharded tier of
    `repro.atomics.execute`.  Parent selection is identical to the
    single-device `bfs` because the arrival-order contract serializes edges
    in (device-rank, local) order — exactly the concatenated edge order of
    the unsharded run.

    ``op`` picks the combiner protocol, mirroring `bfs`:

    ``"cas"``  set-if-unvisited (`Cas(dst, src, expected=-1)`): per-device
               pre-combine (one CAS per distinct destination survives),
               owner-shard resolve, table-only fast path.
    ``"swp"``  swap + revert: pass 1 swaps unconditionally and fetches the
               overwritten parents; pass 2 restores already-visited nodes
               by replaying the revert stream **globally reversed** —
               locally reversed batches under ``reverse_ranks=True``
               (descending device rank), so last-wins of the reversed
               stream equals first-wins of the forward stream, exactly
               the single-device scheme.
    """
    if op not in ("cas", "swp"):
        raise ValueError(f"bfs_sharded supports op 'cas' or 'swp', "
                         f"got {op!r}")
    if mesh is None:
        mesh = make_mesh((jax.device_count(),), (axis,))
    ndev = int(mesh.shape[axis])
    n_pad = -(-n // ndev) * ndev
    e_pad = -(-len(src) // ndev) * ndev
    srcp = np.full((e_pad,), n_pad, np.int32)
    dstp = np.full((e_pad,), n_pad, np.int32)
    srcp[:len(src)] = np.asarray(src, np.int32)
    dstp[:len(dst)] = np.asarray(dst, np.int32)
    parent0 = jnp.full((n_pad,), -1, jnp.int32).at[root].set(root)
    frontier0 = jnp.zeros((n_pad,), bool).at[root].set(True)
    P = jax.sharding.PartitionSpec

    def shard_fn(parent, frontier, s, d):
        def body(state):
            parent, frontier, lvl, edges, _ = state
            fg = jax.lax.all_gather(frontier, axis, tiled=True)  # (n_pad,)
            active = fg[jnp.clip(s, 0, n_pad - 1)] & (s < n_pad)
            cand = jnp.where(active, d, n_pad)                   # OOR drops
            tbl = atomics.AtomicTable(parent, axis=axis)
            if op == "cas":
                res = atomics.execute(
                    tbl, atomics.Cas(cand, s, expected=jnp.int32(-1)),
                    strategy=strategy, need_fetched=False)
                new_parent = res.table.data
            else:  # swp + revert (see docstring)
                res = atomics.execute(tbl, atomics.Swp(cand, s),
                                      strategy=strategy)
                visited_before = res.fetched != -1
                revert_idx = jnp.where(visited_before, cand, n_pad)
                new_parent = atomics.execute(
                    res.table,
                    atomics.Swp(revert_idx[::-1], res.fetched[::-1]),
                    strategy=strategy, need_fetched=False,
                    reverse_ranks=True).table.data
            newf = (new_parent != -1) & (parent == -1)
            edges = edges + jax.lax.psum(jnp.sum(active), axis)
            more = jax.lax.psum(jnp.sum(newf), axis) > 0
            return new_parent, newf, lvl + jnp.int32(1), edges, more
        def cond(state):
            _, _, lvl, _, more = state
            return more & (lvl < max_levels)
        parent, _, lvl, edges, _ = jax.lax.while_loop(
            cond, body, (parent, frontier, jnp.int32(0), jnp.int32(0),
                         jnp.array(True)))
        return parent, lvl[None], edges[None]

    mapped = shard_map_compat(shard_fn, mesh,
                              (P(axis), P(axis), P(axis), P(axis)),
                              (P(axis), P(axis), P(axis)))
    parent, lvl, edges = jax.jit(mapped)(parent0, frontier0,
                                         jnp.asarray(srcp), jnp.asarray(dstp))
    return BfsResult(parent=parent[:n], levels=int(lvl[0]),
                     edges_traversed=int(edges[0]))


def validate_parents(src: np.ndarray, dst: np.ndarray, parent: np.ndarray,
                     root: int) -> bool:
    """Every reached vertex's parent edge must exist; root is its own parent.

    Vectorized: edges and claimed parent edges are encoded as int64 keys
    ``u * n + v`` and matched with `np.isin` (a Graph500-scale edge list
    does not fit a Python set)."""
    parent = np.asarray(parent, np.int64)
    if parent[root] != root:
        return False
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    n = np.int64(max(parent.shape[0], int(src.max(initial=0)) + 1,
                     int(dst.max(initial=0)) + 1))
    reached = np.nonzero(parent >= 0)[0]
    reached = reached[reached != root]
    if parent[reached].max(initial=0) >= n:
        return False
    return bool(np.isin(parent[reached] * n + reached,
                        src * n + dst).all())
