"""Counter steps on a table sharded by rows over a mesh of chips.

Each chip issues its own batch of fetch-and-add ops over the whole key
space; one jitted ``shard_map`` step sends each op to the shard that owns
its slot through `repro.atomics.execute` on an ``AtomicTable`` with mesh
axes, returns every op's fetched value, and donates the table.  The
serialized order is the batches concatenated in device rank order.  Cell
keys: ``ops_per_chip``, ``pool_batches``, ``keys``, ``value_max``,
``strategy``.  Configuration keys: ``mesh``, ``mesh_axes``.
"""

from __future__ import annotations

import time

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from bench import counters
from bench.harness import Record, span


def setup(ctx):
    from repro.sharding import make_mesh
    cell, cfg = ctx.cell, ctx.config
    if cfg.get("op", "faa") != "faa":
        raise ValueError("counters_sharded runs fetch-and-add only")
    axes = tuple(cfg["mesh_axes"])
    mesh = make_mesh(tuple(cfg["mesh"]), axes, devices=ctx.devices)
    sharding = NamedSharding(mesh, P(axes))
    n = int(cell["ops_per_chip"]) * len(ctx.devices)
    idx, vals, distinct = counters.make_pool(ctx, n, sharding=sharding)
    state = dict(ctx=ctx, idx=idx, vals=vals, distinct=distinct, n=n,
                 sharding=sharding, step=_step(mesh, axes, cell))
    table = _table0(state)
    for i in range(min(2, len(idx))):              # compile, then settle
        table, fetched = state["step"](table, idx[i], vals[i])
    jax.block_until_ready((table, fetched))
    state["table"] = _table0(state)         # the warm-up consumed the first
    jax.block_until_ready(state["table"])
    return state


def _table0(state):
    cfg = state["ctx"].config
    fn = jax.jit(counters.start_table.__wrapped__,
                 static_argnames=("m", "low", "high"),
                 out_shardings=state["sharding"])
    return fn(counters.seed_key(state["ctx"].seed, 0), m=int(cfg["slots"]),
              low=int(cfg["initial_min"]), high=int(cfg["initial_max"]) + 1)


def _step(mesh, axes, cell):
    from repro.sharding import shard_map_compat
    spec = P(axes)
    strategy = cell.get("strategy", "auto")

    def body(table, idx, vals):
        from repro import atomics
        res = atomics.execute(atomics.AtomicTable(table, axis=axes),
                              atomics.Faa(idx, vals), strategy=strategy)
        return res.table.data, res.fetched

    return jax.jit(shard_map_compat(body, mesh, (spec, spec, spec),
                                    (spec, spec)), donate_argnums=(0,))


def window(state, seconds: float) -> Record:
    table = state.pop("table")
    sampler = counters.Sampler(state["ctx"].seed, size=int(
        state["ctx"].cell.get("checked_batches", 6)) - 3)
    idx, vals, step = state["idx"], state["vals"], state["step"]
    pool = len(idx)
    k = 0
    start = time.perf_counter()
    while True:
        i = k % pool
        with span("bench.step"):
            table, fetched = step(table, idx[i], vals[i])
            jax.block_until_ready((table, fetched))
        now = time.perf_counter()
        sampler.offer(k, fetched)
        k += 1
        if now - start >= seconds:
            break
    state.update(final=table, sampler=sampler, batches=k)
    ops = k * state["n"]
    return Record(attempted=ops, failed=0, window_s=now - start,
                  e2e={"ops_per_s": ops / (now - start)},
                  extra={"steps": k, "n": state["n"],
                         "distinct": state["distinct"]})


def check(state, record: Record):
    final = np.asarray(state.pop("final"))
    fetched = state.pop("sampler").host()
    pool_idx = [np.asarray(a) for a in state.pop("idx")]
    pool_vals = [np.asarray(a) for a in state.pop("vals")]
    state.pop("step")
    table0 = np.asarray(_table0(state))
    return counters.check(table0, final, pool_idx, pool_vals,
                          state["batches"], fetched)
