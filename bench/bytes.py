"""The least bytes a unit of work has to move, from its shapes and counts.

These count what the work needs, not what an implementation does, so that
every backend is held to the same numerator of its roofline share.
"""

from __future__ import annotations

#: bytes of one int32 word
WORD = 4


def counter_batch(n_ops: int, distinct_slots: int, *,
                  fetched: bool = True) -> int:
    """One batch of ``n_ops`` read-modify-writes on a table of 32-bit words:
    each op's index and value are read once and, with ``fetched``, its
    fetched value written once; each distinct slot the batch touches is read
    once and written once."""
    per_op = WORD * (3 if fetched else 2)
    return n_ops * per_op + 2 * WORD * distinct_slots


def traversal(n_directed_edges: int, n_vertices: int) -> int:
    """One breadth-first traversal of an edge list: each directed edge's two
    32-bit endpoints read once, each vertex's 32-bit parent written once."""
    return 2 * WORD * n_directed_edges + WORD * n_vertices
