"""Device milliseconds per batch in the sort backend's permutation: ops
in the scopes ``rmw.sort`` (argsort, inverse permutation, sorted gathers)
and ``rmw.unsort`` (fetched values back to arrival order), in
`core/rmw.py`.  Summed over the batches (host spans ``bench.batch``)
wholly inside the traced window, over their count."""

from bench import scopes


def read(trace, record, ctx):
    batches = scopes.spans_in_window(trace, "bench.batch")
    ops = scopes.first_device_ops(trace, scopes.of(trace)) if batches else []
    if not ops:
        return None
    s = scopes.leaf_time_s(ops, batches, lambda op: scopes.in_scope(
        op, "rmw.sort") or scopes.in_scope(op, "rmw.unsort"))
    if s <= 0:
        return None
    return 1e3 * s / len(batches)
