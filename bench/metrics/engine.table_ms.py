"""Device milliseconds per batch in the accesses to the table: ops in the
scopes ``rmw.gather`` (each op's slot read) and ``rmw.scatter`` (the table
update), in `core/rmw.py` and `core/rmw_engine.py`, copies left out (they
are `engine.copy_ms`).  Summed over the batches (host spans
``bench.batch``) wholly inside the traced window, over their count."""

from bench import scopes


def read(trace, record, ctx):
    batches = scopes.spans_in_window(trace, "bench.batch")
    ops = scopes.first_device_ops(trace, scopes.of(trace)) if batches else []
    if not ops:
        return None
    s = scopes.leaf_time_s(ops, batches, lambda op: not scopes.is_copy(op)
                           and (scopes.in_scope(op, "rmw.gather")
                                or scopes.in_scope(op, "rmw.scatter")))
    if s <= 0:
        return None
    return 1e3 * s / len(batches)
