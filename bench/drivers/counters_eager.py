"""Counter batches through eager `repro.atomics.execute` on a bare table.

The call a counter service makes: one batch of fetch-and-add ops at a time
on one chip, each call returning the new table and every op's fetched value
(closed loop, one batch in flight).  Cell keys: ``ops_per_batch``,
``pool_batches``, ``keys`` (``zipf`` | ``uniform``), ``value_max``,
``backend``.
"""

from __future__ import annotations

import time

import jax
import numpy as np

from bench import counters
from bench.harness import Record, span


def setup(ctx):
    from repro import atomics  # noqa: F401  (imported before the window)
    cell, cfg = ctx.cell, ctx.config
    if cfg.get("op", "faa") != "faa":
        raise ValueError("counters_eager runs fetch-and-add only")
    n = int(cell["ops_per_batch"])
    idx, vals, distinct = counters.make_pool(ctx, n)
    state = dict(ctx=ctx, idx=idx, vals=vals, distinct=distinct, n=n,
                 backend=cell.get("backend", "auto"))
    table = _table0(ctx)
    for i in range(min(2, len(idx))):              # compile, then settle
        res = _call(state, table, i)
        jax.block_until_ready((res.table.data, res.fetched))
        del res                     # two tables live at most, as in the window
    state["table"] = table          # the call leaves its input unchanged
    return state


def _table0(ctx):
    cfg = ctx.config
    return counters.start_table(counters.seed_key(ctx.seed, 0),
                                m=int(cfg["slots"]),
                                low=int(cfg["initial_min"]),
                                high=int(cfg["initial_max"]) + 1)


def _call(state, table, i):
    from repro import atomics
    return atomics.execute(table, atomics.Faa(state["idx"][i],
                                              state["vals"][i]),
                           backend=state["backend"])


def window(state, seconds: float) -> Record:
    table = state.pop("table")
    sampler = counters.Sampler(state["ctx"].seed, size=int(
        state["ctx"].cell.get("checked_batches", 6)) - 3)
    pool = len(state["idx"])
    enqueue, batch_ids = [], []
    k = 0
    start = time.perf_counter()
    while True:
        i = k % pool
        with span("bench.batch"):
            t0 = time.perf_counter()
            with span("bench.enqueue"):
                res = _call(state, table, i)
            t1 = time.perf_counter()
            with span("bench.wait"):
                jax.block_until_ready((res.table.data, res.fetched))
        now = time.perf_counter()
        table = res.table.data
        sampler.offer(k, res.fetched)
        enqueue.append(t1 - t0)
        batch_ids.append(i)
        k += 1
        if now - start >= seconds:
            break
    window_s = now - start
    state.update(final=table, sampler=sampler, batches=k)
    ops = k * state["n"]
    return Record(attempted=ops, failed=0, window_s=window_s,
                  e2e={"ops_per_s": ops / window_s},
                  extra={"enqueue_s": enqueue, "batch_ids": batch_ids,
                         "n": state["n"], "distinct": state["distinct"]})


def check(state, record: Record):
    final = np.asarray(state.pop("final"))
    fetched = state.pop("sampler").host()
    pool_idx = [np.asarray(a) for a in state.pop("idx")]
    pool_vals = [np.asarray(a) for a in state.pop("vals")]
    table0 = np.asarray(_table0(state["ctx"]))
    return counters.check(table0, final, pool_idx, pool_vals,
                          state["batches"], fetched)
