"""Device milliseconds per step in the scope ``exchange.resolve`` of
`core/rmw_sharded.py`: the owner's engine pass over the ops it received,
on its shard of the table (the engine of `core/rmw_engine.py`, as on one
chip).  On the chip that spends the most, over the steps (host spans
``bench.step``) wholly inside the traced window."""

from bench import exchange


def read(trace, record, ctx):
    return exchange.scope_ms(trace, "exchange.resolve")
