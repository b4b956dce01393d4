"""Host time the eager call spends in the backend cost model's pick (the
program's span ``atomics.select``, in `core/rmw_engine.py`), per batch: the
sum over the batches (host spans ``bench.batch``) wholly inside the traced
window over their count, in microseconds.  Nothing to read in a program
that opens no such span."""

from bench import scopes


def read(trace, record, ctx):
    return scopes.host_us_per_batch(trace, "atomics.select")
