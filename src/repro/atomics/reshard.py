"""Elastic table migration: reshard live `AtomicTable`s across mesh changes.

The paper's finding — atomic cost is set by where the line lives, not which
atomic you issue — has a sharp corollary for the distributed tier: because
ownership is a *pure function of (slot, extent)* (owner-major: ``g //
m_local``), changing the mesh never requires replaying the RMW history that
produced the table.  Re-derive the layout under the new extents, move each
slot to its new owner once, and every subsequent `atomics.execute` is
bit-identical to a never-resharded run (the arrival-order contract is a
property of the *current* mesh, re-derived the same way).  This is the
Big Atomics view of migration: relocating the metadata word sets the price,
not the operation stream.

Two executable paths, chosen by the **migration tier** of the
`HardwareSpec` cost model (`select_migration`, the sibling of
`select_backend` / `select_exchange`):

``"exchange"``     in-collective slot exchange: both meshes are live and
                   cover the SAME device set (axis re-arrangement, replica-
                   contract change, shard-count change across a fixed fleet).
                   Each device's old shard is re-wrapped zero-copy onto the
                   new mesh and ONE padded ``all_to_all`` moves every slot
                   directly to its new owner — no host traffic.
``"device_put"``   host-roundtrip: gather the global table to host, place it
                   under the new layout with one ``device_put`` — the
                   `runtime.elastic.reshard_restore` route, and the only
                   path when the old mesh is gone (fleet grew/shrank, or the
                   table came from a checkpoint).

Entry points:

* :func:`plan_reshard` — build a :class:`ReshardPlan` (path + predicted
  costs) without touching data.
* :func:`ReshardPlan.execute` — run the plan on a live table (or host
  array) and return the migrated `AtomicTable`.
* :func:`migrate` — plan + execute in one call (the runtime hook
  `runtime.fault_tolerance` / `runtime.elastic` use).
* :func:`restore_table` — the checkpoint half: rebuild a handle from host
  data under the active mesh (`checkpoint.ckpt.restore` calls this).
* :func:`cost_replay` — what migration is priced against: re-executing an
  op history through the sharded tier (benchmarks/reshard.py validates
  predicted-vs-measured on the 8-fake-device harness).
"""

from __future__ import annotations

import dataclasses
import functools
import math
import time
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import telemetry
from repro.atomics.layout import INT32_SLOTS, TableLayout, norm_axes
from repro.atomics.table import AtomicTable

Array = jax.Array

PATHS = ("exchange", "device_put")


# ---------------------------------------------------------------------------
# Cost model: the migration tier (HardwareSpec constants, like the others)
# ---------------------------------------------------------------------------

def _mesh_axes_of(layout: TableLayout):
    """Price the layout's mesh with the default topology heuristic (outermost
    axis crosses pods when there is more than one level)."""
    from repro.core.rmw_sharded import _mesh_axes
    names = [n for n, _ in layout.mesh_axes]
    sizes = [s for _, s in layout.mesh_axes]
    return _mesh_axes(names, sizes, None)


def _itemsize(layout: TableLayout) -> int:
    return jnp.dtype(layout.dtype).itemsize


def cost_migrate_exchange(spec, src: TableLayout, dst: TableLayout) -> float:
    """One padded all_to_all over the destination mesh: per-device payload is
    ``n_dev`` lanes of ``min(m_local_src, m_local_dst)`` slots."""
    from repro.core.rmw_sharded import _a2a_s
    n_dev = math.prod(s for _, s in dst.mesh_axes) or 1
    cap = min(src.m_local, dst.m_local)
    return _a2a_s(spec, n_dev * cap * _itemsize(dst), _mesh_axes_of(dst))


def cost_migrate_device_put(spec, src: TableLayout,
                            dst: TableLayout) -> float:
    """Host roundtrip: the whole table crosses the host link twice (gather
    down, scatter up) plus one placement dispatch per shard copy."""
    from repro.core.placement import Tier
    nbytes = dst.num_slots * _itemsize(dst)
    host_bw = getattr(spec, "host_roundtrip_Bps", 0.0) \
        or spec.tier_bandwidth_Bps[Tier.HOST]
    launch = getattr(spec, "device_put_launch_s", 0.0) or 1e-4
    copies = max(1, dst.n_shards * dst.n_replicas)
    return 2.0 * nbytes / host_bw + launch * (1 + math.log2(max(2, copies)))


MIGRATION_COSTS = {
    "exchange": cost_migrate_exchange,
    "device_put": cost_migrate_device_put,
}


def cost_replay(spec, dst: TableLayout, n_ops_total: int, *,
                op: str = "faa", n_batches: int = 1,
                need_fetched: bool = True) -> float:
    """Price of the alternative migration strategy: start from the initial
    table on the new mesh and re-execute the recorded op history through the
    sharded tier (one-shot exchange per batch).  Migration must beat this
    for any history that touched the table more than trivially — the
    acceptance gate of ``benchmarks/reshard.py``."""
    from repro.core.rmw_sharded import cost_exchange_oneshot
    axes = _mesh_axes_of(dst)
    n_dev = math.prod(s for _, s in dst.mesh_axes) or 1
    n_per = max(1, -(-n_ops_total // max(1, n_batches) // n_dev))
    per_batch = cost_exchange_oneshot(spec, op, n_per, dst.num_slots, axes,
                                      need_fetched)
    return n_batches * per_batch


def select_migration(src: TableLayout, dst: TableLayout, *,
                     exchange_feasible: bool, spec=None) -> str:
    """Cheapest feasible migration path — the migration tier of the paper's
    L(A, S) decision procedure (`select_backend` / `select_exchange`'s
    sibling).  ``exchange_feasible`` is topology truth (both meshes live on
    one device set), not a preference; the model only arbitrates when both
    paths can run."""
    if not exchange_feasible:
        return "device_put"
    from repro.core import rmw_engine
    spec = spec or rmw_engine.default_spec()
    return min(MIGRATION_COSTS,
               key=lambda p: MIGRATION_COSTS[p](spec, src, dst))


# ---------------------------------------------------------------------------
# The plan
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ReshardPlan:
    """One planned migration: layouts, chosen path, predicted costs.

    Build with :func:`plan_reshard`; run with :meth:`execute`.  The plan is
    data-independent — the same plan can migrate any table matching ``src``
    (the benchmark reuses one plan across timing reps).
    """

    src: TableLayout
    dst: TableLayout
    path: str                      # "exchange" | "device_put"
    predicted_s: Dict[str, float]  # per-path model predictions (inf = infeasible)
    dst_mesh: object = dataclasses.field(repr=False, default=None)
    src_mesh: object = dataclasses.field(repr=False, default=None)

    def execute(self, table) -> AtomicTable:
        """Migrate ``table`` (an `AtomicTable`, live array, or host array in
        the ``src`` layout) onto the destination mesh.  Returns the handle
        carrying the re-derived contract; contents are bit-identical slot
        for slot."""
        data = table.data if isinstance(table, AtomicTable) else table
        if int(data.shape[0]) != self.src.num_slots:
            raise ValueError(f"table has {data.shape[0]} slots; plan expects "
                             f"{self.src.num_slots}")
        if self.path == "exchange":
            out = _exchange_slots(data, self.src, self.dst,
                                  self.src_mesh, self.dst_mesh)
        else:
            out = _device_put_slots(data, self.dst, self.dst_mesh)
        return AtomicTable(out, axis=self.dst.axis or None,
                           replica_axes=self.dst.replica_axes)


def _same_device_set(mesh_a, mesh_b) -> bool:
    if mesh_a is None or mesh_b is None:
        return False
    return set(mesh_a.devices.flat) == set(mesh_b.devices.flat)


def plan_reshard(src: TableLayout, dst: TableLayout, *, dst_mesh,
                 src_mesh=None, live: bool = True, path: str = "auto",
                 spec=None) -> ReshardPlan:
    """Plan a migration from layout ``src`` to layout ``dst``.

    ``live`` says the source table still exists on devices of ``src_mesh``
    (False for checkpointed host data — only ``device_put`` can run).
    ``path`` forces a specific path ("auto" = `select_migration`).
    """
    if src.num_slots != dst.num_slots:
        raise ValueError(
            f"slot-count changes are not migrations ({src.num_slots} -> "
            f"{dst.num_slots}); grow the table first, then reshard")
    if path == "exchange" and src.num_slots > INT32_SLOTS:
        raise ValueError(
            f"path='exchange' computes int32 slot offsets, up to "
            f"{INT32_SLOTS}; this table has {src.num_slots}: use "
            f"'device_put'")
    feasible = bool(live and dst.is_sharded and src.is_sharded
                    and src.num_slots <= INT32_SLOTS
                    and _same_device_set(src_mesh, dst_mesh))
    from repro.core import rmw_engine
    spec = spec or rmw_engine.default_spec()
    predicted = {
        "exchange": (cost_migrate_exchange(spec, src, dst)
                     if feasible else float("inf")),
        "device_put": cost_migrate_device_put(spec, src, dst),
    }
    if path == "auto":
        # the plan's choice IS its stored predictions (infeasible = inf)
        path = min(predicted, key=predicted.get)
    elif path not in PATHS:
        raise ValueError(f"unknown path {path!r}; have {PATHS}")
    elif path == "exchange" and not feasible:
        raise ValueError(
            "path='exchange' needs both meshes live on the same device set "
            "(use 'device_put' when the fleet changed or the source is a "
            "checkpoint)")
    return ReshardPlan(src=src, dst=dst, path=path, predicted_s=predicted,
                       dst_mesh=dst_mesh, src_mesh=src_mesh)


# ---------------------------------------------------------------------------
# Path 1: in-collective slot exchange (same device set, both meshes live)
# ---------------------------------------------------------------------------

def _shards_by_device(data: Array) -> Dict:
    return {sh.device: sh.data for sh in data.addressable_shards}

def _wrap_on_mesh(shape, sharding, per_device) -> Array:
    """Zero-copy re-wrap of per-device buffers as one logical array."""
    return jax.make_array_from_single_device_arrays(shape, sharding,
                                                    per_device)


@functools.lru_cache(maxsize=64)
def _exchange_executable(src: TableLayout, dst: TableLayout,
                         src_mesh, dst_mesh):
    """Build (once per plan — layouts and meshes are hashable, so repeat
    migrations reuse the compiled collective) the jitted shard_map that
    moves every slot to its new owner with ONE padded all_to_all.

    Because both layouts are contiguous owner-major splits of the same
    ``[0, m)`` slot range, the rows any (old shard, new shard) pair
    exchanges form one contiguous run of at most ``min(m_a, m_b)`` slots —
    so a fixed-cap padded exchange is exact, never truncating.  Replicated
    source shards are deduplicated by a designated *primary* sender (lowest
    old device rank holding the shard); replicated destinations each
    receive their own copy because every lane is per-device.
    """
    n_dev = int(dst_mesh.devices.size)
    m_a, m_b = src.m_local, dst.m_local
    cap = min(m_a, m_b)
    flat_axes = tuple(dst_mesh.axis_names)

    # per-new-flat-rank constants (numpy, baked into the traced body)
    old_devs = list(src_mesh.devices.flat)
    old_flat_of = np.array([old_devs.index(d) for d in dst_mesh.devices.flat])
    src_shard = np.array([src.shard_of_device(int(f)) for f in old_flat_of])
    first_holder: Dict[int, int] = {}
    for f in range(len(old_devs)):   # lowest old device rank wins
        first_holder.setdefault(src.shard_of_device(f), f)
    src_primary = np.array([first_holder[int(s)] == int(f)
                            for s, f in zip(src_shard, old_flat_of)])
    dst_shard = np.array([dst.shard_of_device(j) for j in range(n_dev)])
    sizes = [s for _, s in dst.mesh_axes]

    def body(x):                     # x: (m_a,) — this device's old shard
        j = jnp.zeros((), jnp.int32)
        for name, size in zip(flat_axes, sizes):
            j = j * size + jax.lax.axis_index(name)
        r_me = jnp.asarray(src_shard)[j]
        prim_me = jnp.asarray(src_primary)[j]
        s_me = jnp.asarray(dst_shard)[j]
        lane = jnp.arange(n_dev)
        p = jnp.arange(cap)

        # send: lane k gets the run of my old shard owned by k's new shard
        s_k = jnp.asarray(dst_shard)[lane]
        o = jnp.maximum(r_me * m_a, s_k * m_b)
        ln = jnp.minimum((r_me + 1) * m_a, (s_k + 1) * m_b) - o
        rows = o[:, None] - r_me * m_a + p[None, :]
        send = jnp.where((p[None, :] < ln[:, None]) & prim_me,
                         x[jnp.clip(rows, 0, m_a - 1)],
                         jnp.zeros((), x.dtype))
        recv = jax.lax.all_to_all(send, flat_axes, split_axis=0,
                                  concat_axis=0)

        # receive: source i's run lands at its global offset in my new shard
        r_i = jnp.asarray(src_shard)[lane]
        prim_i = jnp.asarray(src_primary)[lane]
        o_i = jnp.maximum(r_i * m_a, s_me * m_b)
        ln_i = jnp.minimum((r_i + 1) * m_a, (s_me + 1) * m_b) - o_i
        rows_i = o_i[:, None] - s_me * m_b + p[None, :]
        valid = (p[None, :] < ln_i[:, None]) & prim_i[:, None]
        out = jnp.zeros((m_b + 1,), x.dtype).at[
            jnp.where(valid, rows_i, m_b)].set(recv)[:-1]
        return out

    from repro.sharding import shard_map_compat
    return jax.jit(shard_map_compat(body, dst_mesh,
                                    (P(flat_axes),), P(flat_axes)))


def _exchange_slots(data: Array, src: TableLayout, dst: TableLayout,
                    src_mesh, dst_mesh) -> Array:
    """Run the in-collective exchange: zero-copy re-wrap of the old
    per-device shards onto the new mesh, the cached jitted all_to_all, and
    a zero-copy re-wrap of the outputs under the destination sharding."""
    n_dev = int(dst_mesh.devices.size)
    view = _wrap_on_mesh(
        (n_dev * src.m_local,), NamedSharding(dst_mesh,
                                              P(tuple(dst_mesh.axis_names))),
        [_shards_by_device(data)[d] for d in dst_mesh.devices.flat])
    outv = _exchange_executable(src, dst, src_mesh, dst_mesh)(view)
    per_dev = _shards_by_device(outv)
    return _wrap_on_mesh((src.num_slots,), dst.named_sharding(dst_mesh),
                         [per_dev[d] for d in dst_mesh.devices.flat])


# ---------------------------------------------------------------------------
# Path 2: host roundtrip (the elastic.reshard_restore route)
# ---------------------------------------------------------------------------

def _device_put_slots(data, dst: TableLayout, dst_mesh) -> Array:
    host = np.asarray(data)          # gathers a live sharded array too
    if not dst.is_sharded or dst_mesh is None:
        return jnp.asarray(host)
    return jax.device_put(host, dst.named_sharding(dst_mesh))


# ---------------------------------------------------------------------------
# Front doors
# ---------------------------------------------------------------------------

def migrate(table: AtomicTable, dst_mesh, *, axis: object = "auto",
            replica_axes=None, path: str = "auto", spec=None,
            src_mesh=None) -> AtomicTable:
    """Reshard a live table onto ``dst_mesh``, re-deriving the owner-major
    layout, replica contract, and arrival order under the new extents.

    ``axis="auto"`` keeps the table's axis names that still exist on the
    new mesh (the grow/shrink case: same names, new extents); pass explicit
    ``axis=`` / ``replica_axes=`` to change the contract itself.  Results
    of every subsequent `atomics.execute` on the returned handle are
    bit-identical to a run that was never resharded.

    When the re-derived layout cannot be hosted — the slot count does not
    divide the new extents, or every sharding axis vanished — the table
    falls back to a *local* handle (host gather, one placement), the same
    divisibility-aware degradation `make_table` and `restore_table` apply,
    so an elastic restart onto an awkward fleet degrades instead of
    crashing the recovery loop.
    """
    src = TableLayout.from_table(table, mesh=src_mesh)
    if src_mesh is None and src.is_sharded:
        src_mesh = getattr(getattr(table.data, "sharding", None), "mesh",
                           None)
    names = set(dst_mesh.axis_names)
    if axis == "auto":
        axis = tuple(a for a in src.axis if a in names)
    rep = norm_axes(table.replica_axes if replica_axes is None
                    else replica_axes)
    rep = tuple(a for a in rep if a in names)
    try:
        dst = TableLayout.from_mesh(dst_mesh, num_slots=src.num_slots,
                                    dtype=src.dtype, axis=axis,
                                    replica_axes=rep)
    except ValueError:               # non-divisible extents -> local
        dst = TableLayout(num_slots=src.num_slots, dtype=src.dtype)
    plan = plan_reshard(src, dst, dst_mesh=dst_mesh, src_mesh=src_mesh,
                        live=True, path=path, spec=spec)
    # one span: the profiler's range of the migration and, when the stream
    # is on, its event (measured against a block on the new table)
    with telemetry.span("atomics.reshard.migrate", path=plan.path) as sp:
        t0 = time.perf_counter()
        out = plan.execute(table)
        if telemetry.enabled():
            jax.block_until_ready(out.data)
            sp.set(tier="migration", n_slots=src.num_slots,
                   src_shards=src.n_shards, dst_shards=dst.n_shards,
                   src_replicas=src.n_replicas, dst_replicas=dst.n_replicas,
                   predicted_s=plan.predicted_s.get(plan.path),
                   predicted_all={k: v for k, v in plan.predicted_s.items()
                                  if math.isfinite(v)},
                   measured_s=time.perf_counter() - t0)
    return out


def restore_table(host_data, *, like: Optional[AtomicTable] = None,
                  meta: Optional[Dict] = None) -> AtomicTable:
    """Rebuild an `AtomicTable` from host data — the old-mesh-is-gone route.

    The *target* contract comes from ``like`` (the handle in the caller's
    ``like`` tree, built under the new mesh) when given, else from the
    checkpointed layout ``meta`` (axis names re-resolved against the active
    mesh — extents are re-derived, never trusted from the writer).  With no
    active mesh, or axes that no longer exist/divide, the table restores
    local — the same divisibility-aware fallback `make_table` applies.
    """
    from repro import sharding as shardlib
    axis = norm_axes(like.axis if like is not None
                     else tuple((meta or {}).get("axis") or ()))
    rep = norm_axes(like.replica_axes if like is not None
                    else tuple((meta or {}).get("replica_axes") or ()))
    mesh = shardlib.active_mesh()
    data = jnp.asarray(host_data)
    if axis and mesh is not None:
        try:
            dst = TableLayout.from_mesh(mesh, num_slots=int(data.shape[0]),
                                        dtype=data.dtype, axis=axis,
                                        replica_axes=rep)
        except ValueError:           # axis gone or non-divisible -> local
            return AtomicTable(data)
        plan = plan_reshard(
            TableLayout(num_slots=dst.num_slots, dtype=dst.dtype),
            dst, dst_mesh=mesh, live=False, path="device_put")
        return plan.execute(data)
    return AtomicTable(data)
