"""Share of the HBM roofline reached by the engine on the window's batches.

Least time: each batch's least bytes (`bench.bytes.counter_batch`, from its
op count and its distinct slots counted at set-up) at the chip's peak HBM
bandwidth.  Device time: busy time inside the batch's host span
``bench.batch``, which ends when the batch's results are ready.  Summed over
the batches wholly inside the traced window, in percent."""

from bench import bytes as least


def read(trace, record, ctx):
    if not trace.devices:
        return None
    busy = trace.span_busy_s("bench.batch", trace.devices[0])
    spans = trace.spans.get("bench.batch", [])
    lo, hi = trace.window
    ids = [i for (s, e), i in zip(spans, record.extra["batch_ids"])
           if lo <= s and e <= hi]
    if not busy or sum(busy) <= 0:
        return None
    n, distinct = record.extra["n"], record.extra["distinct"]
    need = sum(least.counter_batch(n, distinct[i]) for i in ids)
    return 100.0 * need / ctx.peaks.hbm_bytes_per_s / sum(busy)
