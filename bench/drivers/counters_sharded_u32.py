"""Counter steps on a table of up to 2^32 counters sharded by rows over a
mesh of chips, addressed by uint32 slot ids.

As `counters_sharded`: each chip issues its own batch of fetch-and-add ops
over the whole key space; one jitted ``shard_map`` step sends each op to the
shard that owns its slot through `repro.atomics.execute` on an
``AtomicTable`` with mesh axes, returns every op's fetched value, and
donates the table.  The serialized order is the batches concatenated in
device rank order.  What differs:

* keys are uint32, drawn on the host from the seed by YCSB's chooser
  (`zipf_keys`), so they reach every slot of a 2^32 table;
* the start table is made on the device shard by shard, each chip's rows
  from the seed and its shard index, with no table-sized random call; the
  same function makes it again for the check.

Cell keys: ``ops_per_chip``, ``pool_batches``, ``keys`` (``zipf``),
``value_max``, ``strategy``, ``checked_batches``.  Configuration keys:
``slots``, ``mesh``, ``mesh_axes``, ``initial_min``, ``initial_max``,
``zipf_constant``.
"""

from __future__ import annotations

import sys
import time

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from bench import counters
from bench.harness import Record, span
from bench.traffic import zipf


def zipf_keys(rng: np.random.Generator, size: int,
              recordcount: int) -> np.ndarray:
    """``zipf.scrambled_zipf_keys`` over up to 2^32 loaded keys, as uint32:
    its cast from int64 to int32 keeps the low 32 bits of each key."""
    if not 0 < recordcount <= 1 << 32:
        raise ValueError(f"recordcount {recordcount} is not in [1, 2^32]")
    return zipf.scrambled_zipf_keys(rng, size, recordcount).view(np.uint32)


def setup(ctx):
    from repro.atomics.layout import owner_shard
    from repro.sharding import make_mesh
    cell, cfg = ctx.cell, ctx.config
    if cfg.get("op", "faa") != "faa":
        raise ValueError("counters_sharded_u32 runs fetch-and-add only")
    if cell["keys"] != "zipf" or cfg.get("zipf_constant") != zipf.YCSB_THETA:
        raise ValueError("counters_sharded_u32 draws YCSB's zipfian keys "
                         f"(constant {zipf.YCSB_THETA}) only")
    axes = tuple(cfg["mesh_axes"])
    mesh = make_mesh(tuple(cfg["mesh"]), axes, devices=ctx.devices)
    sharding = NamedSharding(mesh, P(axes))
    chips, m = len(ctx.devices), int(cfg["slots"])
    per_chip = int(cell["ops_per_chip"])
    n = per_chip * chips
    pool, vmax = int(cell["pool_batches"]), int(cell["value_max"])
    rng = np.random.default_rng([int(ctx.seed), 1])
    keys = zipf_keys(rng, pool * n, m).reshape(pool, n)
    vals = rng.integers(-vmax, vmax + 1, (pool, n), dtype=np.int32)
    idx = [jax.device_put(a, sharding) for a in keys]
    # per pool batch: the ops each owner resolves and the slots it owns
    m_local = m // chips
    owner_ops, owner_distinct = [], []
    for row, dev in zip(keys, idx):
        owner = np.asarray(owner_shard(dev, m_local, chips))
        owner_ops.append(np.bincount(owner, minlength=chips).tolist())
        owner_distinct.append(np.bincount(
            np.unique(row) // m_local, minlength=chips).tolist())
    top = max(max(o) for o in owner_ops) / n
    print(f"cell={ctx.name} largest owner share of a step's ops: {top:.6f}",
          file=sys.stderr, flush=True)
    state = dict(ctx=ctx, idx=idx,
                 vals=[jax.device_put(a, sharding) for a in vals],
                 n=n, m_local=m_local, mesh=mesh, axes=axes,
                 step=_step(mesh, axes, cell),
                 extra=dict(n=n, ops_per_chip=per_chip,
                            owner_ops=owner_ops,
                            owner_distinct=owner_distinct,
                            shard_of_device={
                                f"TPU:{d.id}": s
                                for s, d in enumerate(mesh.devices.flat)}))
    table = _table0(state)
    for i in range(min(2, len(idx))):              # compile, then settle
        table, fetched = state["step"](table, idx[i], state["vals"][i])
    jax.block_until_ready((table, fetched))
    del table, fetched
    state["table"] = _table0(state)         # the warm-up consumed the first
    jax.block_until_ready(state["table"])
    return state


def _table0(state):
    """The start table, sharded: shard ``s`` holds the counters of
    `counters.start_table` drawn from the seed's key folded with ``s``."""
    from repro.sharding import shard_map_compat
    cfg, axes = state["ctx"].config, state["axes"]

    def shard(key):
        key = jax.random.fold_in(key, jax.lax.axis_index(axes))
        return counters.start_table(key, m=state["m_local"],
                                    low=int(cfg["initial_min"]),
                                    high=int(cfg["initial_max"]) + 1)

    fn = jax.jit(shard_map_compat(shard, state["mesh"], P(), P(axes)))
    return fn(counters.seed_key(state["ctx"].seed, 0))


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
    batch_ids = []
    k = 0
    start = time.perf_counter()
    while True:
        i = k % pool
        with span("bench.step"):
            table, fetched = step(table, idx[i], vals[i])
            jax.block_until_ready((table, fetched))
        now = time.perf_counter()
        sampler.offer(k, fetched)
        batch_ids.append(i)
        k += 1
        if now - start >= seconds:
            break
    state.update(final=table, sampler=sampler, batches=k)
    ops = k * state["n"]
    return Record(attempted=ops, failed=0, window_s=now - start,
                  e2e={"ops_per_s": ops / (now - start)},
                  extra=dict(state["extra"], steps=k, batch_ids=batch_ids))


def check(state, record: Record):
    final = np.asarray(state.pop("final"))
    fetched = state.pop("sampler").host()
    pool_idx = [np.asarray(a) for a in state.pop("idx")]
    pool_vals = [np.asarray(a) for a in state.pop("vals")]
    state.pop("step")
    table0 = np.asarray(_table0(state))
    return counters.check(table0, final, pool_idx, pool_vals,
                          state["batches"], fetched)
