"""Device milliseconds per step in the scope ``exchange.precombine`` of
`core/rmw_sharded.py`: each chip's split of its slot ids into owner and
row, its sort and combine of same-slot ops, and the packing of the send
buffers (both levels of the hierarchical exchange).  On the chip that spends
the most, over the steps (host spans ``bench.step``) wholly inside the
traced window."""

from bench import exchange


def read(trace, record, ctx):
    return exchange.scope_ms(trace, "exchange.precombine")
