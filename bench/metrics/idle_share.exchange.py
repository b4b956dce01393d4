"""Share of the traced window in which no operation runs on a chip of the
sharded cell, in percent; the mean over its chips (each chip's share is on
an earlier line of standard error)."""


def read(trace, record, ctx):
    if not trace.devices or trace.window_s <= 0:
        return None
    shares = [trace.idle_share(d) for d in trace.devices]
    return 100.0 * sum(shares) / len(shares)
