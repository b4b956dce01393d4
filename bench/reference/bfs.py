"""Level-synchronous breadth-first search whose parents are settled by
compare-and-swap in arrival order.

At each level every edge whose source is in the frontier tries
``CAS(parent[dst], expected=-1, new=src)`` in edge-list order: the first
such edge to an unvisited vertex wins, later ones fail.  ``levels`` counts
the level passes run, the last of which finds nothing new.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np


def bfs(src: np.ndarray, dst: np.ndarray, n: int,
        root: int) -> Tuple[np.ndarray, int]:
    src = np.asarray(src)
    dst = np.asarray(dst)
    parent = np.full(n, -1, np.int32)
    parent[root] = root
    frontier = np.zeros(n, bool)
    frontier[root] = True
    levels = 0
    while True:
        levels += 1
        edge = np.flatnonzero(frontier[src])
        edge = edge[parent[dst[edge]] == -1]
        reached, first = np.unique(dst[edge], return_index=True)
        parent[reached] = src[edge[first]]
        if reached.size == 0:
            return parent, levels
        frontier[:] = False
        frontier[reached] = True


def component_tuples(src_tuples: np.ndarray, parent: np.ndarray) -> int:
    """Graph500's count for one traversal: input edge tuples whose endpoints
    lie in the traversed component."""
    return int((np.asarray(parent)[np.asarray(src_tuples)] >= 0).sum())
