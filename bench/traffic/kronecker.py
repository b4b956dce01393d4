"""Graph500's Kronecker edge generator, on the device.

As the Graph500 reference code (``kronecker_generator.m``) defines it: for
each of ``scale`` bit levels, every edge draws its source bit with
``P(1) = 1 - (A + B)`` and then its destination bit with
``P(1) = C / (1 - (A + B))`` after a source 1 and ``B / (A + B)`` after a
source 0, so a pair of bits is (0,0), (0,1), (1,0), (1,1) with
probabilities A, B, C, D.  Vertex labels are then permuted, and so is the
order of the edges.  Self-loops and repeated edges stay, as in the
reference.  Graph500's graph is undirected: `symmetrise` lists each tuple in
both directions for a traversal.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

GRAPH500_ABC = (0.57, 0.19, 0.19)


@partial(jax.jit, static_argnames=("scale", "edgefactor", "permute"))
def edges(key, *, scale: int, edgefactor: int, a: float = 0.57,
          b: float = 0.19, c: float = 0.19, permute: bool = True):
    """(src, dst) int32 arrays of ``edgefactor * 2^scale`` undirected edge
    tuples.  With ``permute=False`` labels and order are left as drawn."""
    m = edgefactor << scale
    ab = a + b
    c_norm = c / (1 - ab)
    a_norm = a / ab
    k_bits, k_vperm = jax.random.split(key)
    src = jnp.zeros((m,), jnp.int32)
    dst = jnp.zeros((m,), jnp.int32)
    for level, k in enumerate(jax.random.split(k_bits, scale)):
        k1, k2 = jax.random.split(k)
        ii = jax.random.uniform(k1, (m,)) > ab
        jj = jax.random.uniform(k2, (m,)) > jnp.where(ii, c_norm, a_norm)
        src = src | (ii.astype(jnp.int32) << level)
        dst = dst | (jj.astype(jnp.int32) << level)
    if permute:
        src, dst, _ = relabel(src, dst, jax.random.fold_in(k_vperm, 0),
                              n=1 << scale)
    return src, dst


@partial(jax.jit, static_argnames=("n",))
def relabel(src, dst, key, *, n: int):
    """Graph500's last two steps: permute the vertex labels and the order of
    the edges.  Returns the new (src, dst) and the label map."""
    k_vperm, k_eperm = jax.random.split(key)
    perm = jax.random.permutation(k_vperm, n).astype(jnp.int32)
    order = jax.random.permutation(k_eperm, src.shape[0])
    return perm[src][order], perm[dst][order], perm


@jax.jit
def symmetrise(src, dst):
    """Directed edge list holding every tuple both ways: the tuples as drawn,
    then each reversed."""
    return (jnp.concatenate([src, dst]), jnp.concatenate([dst, src]))


@partial(jax.jit, static_argnames=("n",))
def has_edge(src, dst, *, n: int):
    """Vertices with at least one edge that is not a self-loop: where
    Graph500 may draw a search key."""
    live = (src != dst).astype(jnp.int32)
    deg = jnp.zeros((n,), jnp.int32).at[src].add(live).at[dst].add(live)
    return deg > 0
