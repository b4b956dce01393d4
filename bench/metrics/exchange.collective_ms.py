"""Device milliseconds per step in collective operations (all-to-all,
all-gather, all-reduce, reduce-scatter, collective-permute), on the chip
that spends the most: its collective time in the traced window over the
steps (host spans ``bench.step``) wholly inside it."""


def read(trace, record, ctx):
    if not trace.devices:
        return None
    lo, hi = trace.window
    steps = [1 for s, e in trace.spans.get("bench.step", [])
             if lo <= s and e <= hi]
    if not steps:
        return None
    worst = max(trace.collective_s(d) for d in trace.devices)
    return 1e3 * worst / len(steps)
