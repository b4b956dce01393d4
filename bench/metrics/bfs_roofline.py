"""Share of the HBM roofline reached by one traversal, the mean over the
traversals wholly inside the traced window, in percent.

Least time: `bench.bytes.traversal` (each directed edge's endpoints read
once, each vertex's parent written once) at the chip's peak HBM bandwidth.
Device time: busy time inside the traversal's host span ``bench.root``."""

from bench import bytes as least


def read(trace, record, ctx):
    if not trace.devices:
        return None
    busy = [b for b in trace.span_busy_s("bench.root", trace.devices[0])
            if b > 0]
    if not busy:
        return None
    need = least.traversal(record.extra["directed_edges"], record.extra["n"])
    t_min = need / ctx.peaks.hbm_bytes_per_s
    return 100.0 * sum(t_min / b for b in busy) / len(busy)
