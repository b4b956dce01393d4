"""Device milliseconds per batch in the copies (``copy.N``, ``copy-start``,
``copy-done``) of the engine's programs: the programs holding an op in an
``rmw.*`` scope.  The compiler inserts them, with no name stack; on the
eager path the chief one is the copy of the undonated table before the
in-place scatter.  Summed over the batches
(host spans ``bench.batch``) wholly inside the traced window, over their
count.  Nothing to read in a program without the ``rmw.*`` scopes."""

from bench import scopes


def read(trace, record, ctx):
    batches = scopes.spans_in_window(trace, "bench.batch")
    ops = scopes.first_device_ops(trace, scopes.of(trace)) if batches else []
    if not ops:
        return None
    engine = scopes.modules_with(ops, "rmw.")
    if not engine:
        return None
    s = scopes.leaf_time_s(ops, batches, lambda op: op.module in engine
                           and scopes.is_copy(op))
    return 1e3 * s / len(batches)
