"""What the readers of a sharded cell's exchange share.

* `scope_ms`: device milliseconds a step inside one ``exchange.*`` scope of
  `repro.core.rmw_sharded`, on the chip that spends the most there, over
  the steps (host spans ``bench.step``) wholly inside the traced window;
* `chip_step_bytes`: the least bytes one chip moves in one step, counted
  as `bench.bytes.counter_batch` counts a one-chip batch: each op the chip
  issues has its slot id and value read once and its fetched value written
  once, and each distinct slot the chip owns in the step is read once and
  written once by the owner.  The exchange's own traffic is left out: it is
  the cost of sharding, not the work asked for.

Both read what `bench/drivers/counters_sharded_u32.py` records; with no
such record, or no scope by that name in the trace, they read nothing.
"""

from __future__ import annotations

from bench import scopes
from bench.bytes import counter_batch


def scope_ms(trace, scope: str):
    steps = scopes.spans_in_window(trace, "bench.step")
    sc = scopes.of(trace) if steps and trace.devices else None
    if sc is None:
        return None
    worst = max(scopes.leaf_time_s(sc.devices.get(d.name, []), steps,
                                   lambda op: scopes.in_scope(op, scope))
                for d in trace.devices)
    return 1e3 * worst / len(steps) if worst > 0 else None


def chip_step_bytes(extra: dict, batch: int, shard: int) -> int:
    """Least bytes of pool batch ``batch`` on the chip holding ``shard``."""
    return counter_batch(extra["ops_per_chip"],
                         extra["owner_distinct"][batch][shard])
