"""Host time the eager call spends calling the jitted backend, up to its
return (the program's span ``atomics.dispatch``, in `core/rmw_engine.py`
and `atomics/execute.py`): argument handling, the runtime's launch and the
output buffers.  Per batch: the sum over the batches (host spans
``bench.batch``) wholly inside the traced window over their count, in
microseconds.  Nothing to read in a program that opens no such span."""

from bench import scopes


def read(trace, record, ctx):
    return scopes.host_us_per_batch(trace, "atomics.dispatch")
