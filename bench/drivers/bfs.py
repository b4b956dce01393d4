"""Graph500 breadth-first search through `repro.core.bfs.bfs`.

The edge tuples and the search keys (vertices with an edge that is not a
self-loop, as Graph500 draws them) and their order come from the
configuration's ``graph_seed``; ``--seed`` draws Graph500's relabelling of
the vertices and its order of the edges.  So every seed traverses the same
graph up to isomorphism from the same keys in the same order, and does the
same work, while the parents that compare-and-swap in arrival order picks
differ.  The keys are traversed back to back, cycling, one traversal in
flight.  Every traversal's parent array is kept; after the window
Graph500's edge count of each traversed component gives ``teps``, and a
seeded sample of the traversals (the first and the last among them) is
compared with `reference.bfs`.  Cell keys: ``op``, ``roots``,
``checked_roots``.
"""

from __future__ import annotations

import random
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.counters import seed_key
from bench.harness import Check, Record, span
from bench.reference import bfs as ref
from bench.traffic import kronecker


def _graph(ctx):
    """The symmetrised edge list, labelled and ordered by the seed, the
    search keys under the seed's labels, and an isolated vertex if any."""
    cfg = ctx.config
    a, b, c = cfg["initiator"]
    n = 1 << int(cfg["scale"])
    src, dst = kronecker.edges(seed_key(int(cfg["graph_seed"]), 2),
                               scale=int(cfg["scale"]),
                               edgefactor=int(cfg["edgefactor"]), a=a, b=b,
                               c=c, permute=False)
    live = np.flatnonzero(np.asarray(kronecker.has_edge(src, dst, n=n)))
    keys = np.random.default_rng([int(cfg["graph_seed"]), 3]).choice(
        live, min(int(ctx.cell["roots"]), live.size), replace=False)
    isolated = np.setdiff1d(np.arange(min(n, live.size + 1)), live)[:1]
    src, dst, perm = kronecker.relabel(src, dst, seed_key(ctx.seed, 4), n=n)
    perm = np.asarray(perm)
    return kronecker.symmetrise(src, dst), perm[keys], perm[isolated]


@partial(jax.jit, static_argnames=("tuples",))
def _component_tuples(parent, src, *, tuples: int):
    return jnp.sum(parent[src[:tuples]] >= 0)


def setup(ctx):
    from repro.core import bfs as bfs_mod  # noqa: F401
    cfg, cell = ctx.config, ctx.cell
    n = 1 << int(cfg["scale"])
    (src2, dst2), roots, isolated = _graph(ctx)
    state = dict(ctx=ctx, n=n, src=src2, dst=dst2, op=cell["op"],
                 roots=[int(r) for r in roots],
                 tuples=int(cfg["edgefactor"]) << int(cfg["scale"]))
    # an isolated vertex's traversal is one level: it compiles the program
    warm = int(isolated[0]) if isolated.size else state["roots"][0]
    res = _bfs(state, warm)
    jax.block_until_ready(res.parent)
    jax.block_until_ready(_component_tuples(res.parent, src2,
                                            tuples=state["tuples"]))
    return state


def _bfs(state, root):
    from repro.core import bfs as bfs_mod
    return bfs_mod.bfs(state["src"], state["dst"], state["n"], root=root,
                       op=state["op"])


def window(state, seconds: float) -> Record:
    roots = state["roots"]
    done, parents, levels = [], [], []
    start = time.perf_counter()
    while True:
        root = roots[len(done) % len(roots)]
        with span("bench.root"):
            res = _bfs(state, root)
            jax.block_until_ready(res.parent)
        now = time.perf_counter()
        done.append(root)
        parents.append(res.parent)
        levels.append(int(res.levels))
        if now - start >= seconds:
            break
    state.update(done=done, parents=parents, levels=levels)
    return Record(attempted=len(done), failed=0, window_s=now - start,
                  extra={"roots": len(done), "levels": levels,
                         "n": state["n"],
                         "directed_edges": int(state["src"].shape[0])})


def check(state, record: Record):
    tuples = state["tuples"]
    counts = [int(_component_tuples(p, state["src"], tuples=tuples))
              for p in state["parents"]]
    record.e2e["teps"] = sum(counts) / record.window_s
    record.extra["component_tuples"] = counts
    done = state.pop("done")
    middle = range(1, len(done) - 1)
    extra = max(0, int(state["ctx"].cell.get("checked_roots", 3)) - 2)
    pick = sorted({0, len(done) - 1} | set(random.Random(
        state["ctx"].seed).sample(middle, min(extra, len(middle)))))
    parents = state.pop("parents")
    got = {j: np.asarray(parents[j]) for j in pick}
    del parents
    src, dst = np.asarray(state.pop("src")), np.asarray(state.pop("dst"))
    parent_diff = level_diff = 0
    for j in pick:
        want, lv = ref.bfs(src, dst, state["n"], done[j])
        parent_diff += int(np.count_nonzero(got[j] != want))
        level_diff += abs(state["levels"][j] - lv)
    return [Check("parent_diff", parent_diff, 0),
            Check("level_diff", level_diff, 0)]
