"""Share of the HBM roofline reached by a sharded step on its busiest chip.

Busiest: the chip with the most device busy time inside the steps (host
spans ``bench.step``) wholly inside the traced window.  Least time: that
chip's least bytes of those steps (`bench.exchange.chip_step_bytes`, from
the ops it issues and the distinct slots it owns, counted at set-up) at the
chip's peak HBM bandwidth.  Over that busy time, in percent."""

from bench import exchange


def read(trace, record, ctx):
    extra = record.extra
    shard_of = extra.get("shard_of_device")
    ids = extra.get("batch_ids")
    if not trace.devices or not shard_of or ids is None:
        return None
    lo, hi = trace.window
    inside = [i for (s, e), i in zip(trace.spans.get("bench.step", []), ids)
              if lo <= s and e <= hi]
    busy = {d.name: sum(trace.span_busy_s("bench.step", d))
            for d in trace.devices if d.name in shard_of}
    if not inside or not busy:
        return None
    name = max(busy, key=busy.get)
    if busy[name] <= 0:
        return None
    need = sum(exchange.chip_step_bytes(extra, i, shard_of[name])
               for i in inside)
    return 100.0 * need / ctx.peaks.hbm_bytes_per_s / busy[name]
