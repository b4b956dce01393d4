"""Host clock from the call into `atomics.execute` until it returns, before
blocking on its results: the mean over the window's batches, in
microseconds (the sum over the window, which spans far more than a host
clock's error, divided by the batch count)."""


def read(trace, record, ctx):
    enqueue = record.extra.get("enqueue_s")
    if not enqueue:
        return None
    return sum(enqueue) / len(enqueue) * 1e6
