"""Mesh-wide sharded atomics: distributed RMW with hierarchical combining.

The paper's contention study (§5.4) shows aggregate atomic bandwidth
*collapsing* when many agents hammer one line, and §6.2 proposes combining
trees and remote-execution atomics as the fix.  This module is that fix at
mesh scale: a batch of FAA/SWP/MIN/MAX/uniform-CAS ops, issued by every
device of a ``shard_map`` against a table **sharded over mesh axes**, executes
as a two-phase *local-combine-then-owner-resolve* protocol whose results are
bit-identical to a single-device serialized oracle under a documented
cross-device arrival order.

Protocol (one exchange level)::

    phase 1 — pre-combine   each device sorts its local batch by global slot
                            and collapses every same-slot group into ONE
                            combined op using the PR-1 engine
                            (`rmw_engine.execute_backend` on an identity
                            table);
                            group combination is closed under every supported
                            op (FAA: sum, SWP: last, MIN/MAX: min/max,
                            uniform-CAS: first value != expected, else
                            expected).
    route                   combined reps are packed into a padded buffer,
                            one lane of `cap` slots per destination, and
                            exchanged with ONE `lax.all_to_all` over the axis.
    phase 2 — resolve       the owner shard applies the received per-device
                            groups (in source-rank order) with a second
                            engine pass; its fetched values are the *bases* —
                            the slot value each group observed.
    return                  bases flow back through the same `all_to_all`
                            and each device reconstructs exact per-op
                            fetched/success values from (base, local chain).

**Arrival-order contract**: results equal `rmw_serialized` applied to the
concatenation of per-device batches ordered by device rank — lexicographic
over ``replica_axes + axis`` (major to minor), each device's ops in local
order.  Every strategy below realizes the *same* order, so they are
interchangeable bit-for-bit.  The contract (and the owner-major slot->shard
arithmetic realizing it) is reified by `repro.atomics.layout.TableLayout`;
``reverse_ranks=True`` flips it to *descending* device rank — with locally
reversed batches that is a globally reversed op stream, which is what the
SWP+revert BFS scheme needs for its second pass.

Strategies (`strategy=`):

``"oneshot"``       one exchange over the flattened ``axis`` tuple.
``"hierarchical"``  two levels for ``axis=(outer, inner...)``: pre-combine
                    within the inner axes to a per-pod deputy (the owner's
                    inner-rank peer), deputies re-combine and exchange over
                    the outer (DCN) axis only — the paper's combining tree,
                    §6.2.3, spanning pods.  Cross-pod traffic shrinks from
                    ``n_devices·cap`` to ``n_pods·min(...)`` rows.
``"naive"``         no pre-combining: every op routed individually (the
                    paper's measured serialized regime; benchmark baseline).
``"dense"``         pure-FAA table-only degenerate path: local bincount +
                    `psum_scatter` (+ `psum` over replica axes).
``"auto"``          `select_exchange` picks the cheapest strategy from the
                    `HardwareSpec` ICI/DCN exchange terms + the PR-1 backend
                    cost models — the executable form of the paper's Fig. 8
                    crossover.

Out-of-range indices are dropped (fetched 0 / success False), matching the
engine's convention.  CAS supports both expected forms: the combinable
*uniform* scalar (all strategies above) and **per-op expected arrays**,
which cannot be pre-combined (the paper's "wasted work" case) and instead
route every op raw to its owner for a serialized-oracle pass
(`_execute_cas_perop` — the owner-side form of the paper's §6.2
remote-execution atomics).

All entry points must be called INSIDE `shard_map` (they use collectives
over the named axes); the public spelling is `repro.atomics.execute`, which
auto-detects that context.  `indices` are **global** slot ids; the table
argument is the caller's local shard (owner-major layout: global slot ``g``
lives on shard ``g // m_local`` at row ``g % m_local``).
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

from repro.atomics.layout import local_row, owner_shard
from repro.core import collective_model, perf_model, rmw_engine
from repro.core.collective_model import MeshAxis
from repro.core.placement import Tier
from repro.core.rmw import OPS, RmwResult, _identity

Array = jax.Array
AxisNames = Union[str, Tuple[str, ...]]

STRATEGIES = ("auto", "oneshot", "hierarchical", "naive", "dense")

#: bytes moved per routed op on the wire (int32 slot id + 4-byte value)
ROW_BYTES = 8


def _axes_tuple(axis: AxisNames) -> Tuple[str, ...]:
    return (axis,) if isinstance(axis, str) else tuple(axis)


def _axis_size(axis: AxisNames) -> int:
    """Static size of a (possibly tuple) mesh axis inside shard_map."""
    return int(jax.lax.psum(1, _axes_tuple(axis)))


# ---------------------------------------------------------------------------
# Phase 1 machinery: sort, pre-combine, pack, reconstruct
# ---------------------------------------------------------------------------

class _Combined(NamedTuple):
    """Bookkeeping of one local pre-combine (all arrays in sorted order)."""

    order: Array        # argsort of the input batch by global slot
    inv: Array          # inverse permutation
    sidx: Array         # sorted global slot ids (invalid == m_global)
    sval: Array         # sorted values
    seg_start: Array    # True at the first op of each same-slot group
    seg_id: Array       # compressed group index per op
    combined: Array     # (n,) combined value per group, dense by seg_id
    loc_fetched: Array  # per-op fetched vs the identity base (None if !need)
    loc_success: Array  # per-op success vs the identity base


def _identity_base(op: str, dtype, expected) -> Array:
    if op == "cas":
        return jnp.asarray(expected, dtype)
    if op in ("min", "max"):
        return _identity(op, dtype)
    return jnp.zeros((), dtype)  # faa, swp (swp base unused: seg_start flags)


def _combine(gidx: Array, vals: Array, op: str, expected, *,
             need_fetched: bool, backend: str, spec) -> _Combined:
    """Collapse a flat batch into one combined op per distinct slot.

    The per-group combine *and* the per-op local chain (fetched/success
    relative to an identity base) come from a single PR-1 engine pass against
    a dense identity table indexed by compressed group id — group combination
    is closed under every supported op, which is what makes the whole
    hierarchy self-similar.
    """
    n = gidx.shape[0]
    order = jnp.argsort(gidx, stable=True)
    inv = jnp.zeros_like(order).at[order].set(
        jnp.arange(n, dtype=order.dtype))
    sidx = gidx[order]
    sval = vals[order]
    seg_start = jnp.concatenate(
        [jnp.ones((1,), bool), sidx[1:] != sidx[:-1]])
    seg_id = jnp.cumsum(seg_start.astype(jnp.int32)) - 1
    ident = jnp.full((n,), _identity_base(op, vals.dtype, expected),
                     vals.dtype)
    exp = None if op != "cas" else jnp.asarray(expected, vals.dtype)
    res = rmw_engine.execute_backend(ident, seg_id, sval, op, exp,
                                     backend=backend, spec=spec,
                                     need_fetched=need_fetched)
    return _Combined(order=order, inv=inv, sidx=sidx, sval=sval,
                     seg_start=seg_start, seg_id=seg_id, combined=res.table,
                     loc_fetched=res.fetched, loc_success=res.success)


class _Stage(NamedTuple):
    """One routed exchange level (pack state kept for the return path)."""

    axis: AxisNames
    n_dest: int
    cap: int
    comb: _Combined
    slotpos: Array      # per-op packed buffer position (scratch if not rep)
    m_global: int
    reverse: bool = False


def _flip_lanes(x: Array, n_dest: int, cap: int) -> Array:
    """Reverse the per-source blocks of a routed flat buffer: the receiver
    processes sources in *descending* rank — the reversed arrival order.
    Involutive, so the return path applies the same flip to undo it."""
    return x.reshape(n_dest, cap)[::-1].reshape(-1)


def _rank_slotpos(dest: Array, valid: Array, n_dest: int, cap: int) -> Array:
    """Packed-exchange position per op: lane = destination rank, row = the
    op's arrival rank among same-destination valid ops (the engine's own
    sort-free FAA-fetch rank, so lanes fill densely in local order — the
    arrival-order contract), scratch (= n_dest * cap) for invalid ops.

    The single home for this packing: the combined (`_push`), naive
    (`_push_naive`) and per-op-CAS (`_push_uncombined`) paths all route
    through it, so the scratch/OOR convention cannot diverge between them.
    """
    key = jnp.where(valid, dest, n_dest)
    rank = rmw_engine._arrival_rank_sortfree(key, n_dest + 1)
    return jnp.where(valid, dest * cap + rank, n_dest * cap)


def _scatter_padded(fill, dtype, slotpos: Array, x: Array,
                    size: int) -> Array:
    """Scatter ``x`` to ``slotpos`` in a ``fill``-initialized (size,)
    buffer; position ``size`` is the dropped scratch row."""
    return jnp.full((size + 1,), fill, dtype).at[slotpos].set(x)[:-1]


def _route_pair(send_idx: Array, send_val: Array, axis: AxisNames,
                n_dest: int, cap: int) -> Tuple[Array, Array]:
    """Move (slot id, combined value) rows with ONE all_to_all.

    4-byte value dtypes ride in the same buffer as the int32 ids (bitcast),
    matching the cost model's single-launch ROW_BYTES pricing; wider dtypes
    fall back to a second collective."""
    if send_val.dtype.itemsize == 4:
        bits = jax.lax.bitcast_convert_type(send_val, jnp.int32)
        packed = jnp.stack([send_idx, bits], axis=-1).reshape(n_dest, cap, 2)
        recv = jax.lax.all_to_all(packed, axis, split_axis=0,
                                  concat_axis=0).reshape(-1, 2)
        return recv[:, 0], jax.lax.bitcast_convert_type(recv[:, 1],
                                                        send_val.dtype)
    recv_idx = jax.lax.all_to_all(send_idx.reshape(n_dest, cap), axis,
                                  split_axis=0, concat_axis=0).reshape(-1)
    recv_val = jax.lax.all_to_all(send_val.reshape(n_dest, cap), axis,
                                  split_axis=0, concat_axis=0).reshape(-1)
    return recv_idx, recv_val


def _push(gidx: Array, vals: Array, op: str, expected, *, axis: AxisNames,
          n_dest: int, dest: Array, cap: int, m_global: int,
          need_fetched: bool, backend: str, spec, reverse: bool = False
          ) -> Tuple[_Stage, Array, Array]:
    """Pre-combine + route one level.  `dest` gives, per op, the destination
    rank on `axis` (same for every op of a group).  Returns the stage record
    and the received flat batch (source-rank-major — the arrival order;
    descending source rank when ``reverse``)."""
    st = _combine(gidx, vals, op, expected, need_fetched=need_fetched,
                  backend=backend, spec=spec)
    dest_s = dest[st.order]
    valid = st.sidx < m_global
    is_rep = st.seg_start & valid
    scratch = n_dest * cap
    slotpos = _rank_slotpos(dest_s, is_rep, n_dest, cap)
    send_idx = _scatter_padded(m_global, jnp.int32, slotpos,
                               jnp.where(is_rep, st.sidx, m_global), scratch)
    send_val = _scatter_padded(0, vals.dtype, slotpos,
                               st.combined[st.seg_id], scratch)
    recv_idx, recv_val = _route_pair(send_idx, send_val, axis, n_dest, cap)
    if reverse:
        recv_idx = _flip_lanes(recv_idx, n_dest, cap)
        recv_val = _flip_lanes(recv_val, n_dest, cap)
    stage = _Stage(axis=axis, n_dest=n_dest, cap=cap, comb=st,
                   slotpos=slotpos, m_global=m_global, reverse=reverse)
    return stage, recv_idx, recv_val


def _pop(stage: _Stage, bases_recv: Array, op: str, expected
         ) -> Tuple[Array, Array]:
    """Return one level: route the resolver's bases back to the sources and
    reconstruct exact per-op fetched/success from (base, local chain)."""
    st = stage.comb
    n = st.sidx.shape[0]
    if stage.reverse:       # undo the receive-side flip before routing back
        bases_recv = _flip_lanes(bases_recv, stage.n_dest, stage.cap)
    ret = jax.lax.all_to_all(bases_recv.reshape(stage.n_dest, stage.cap),
                             stage.axis, split_axis=0,
                             concat_axis=0).reshape(-1)
    ret = jnp.concatenate([ret, jnp.zeros((1,), ret.dtype)])
    base_rep = ret[stage.slotpos]                     # scratch -> 0
    base_seg = jnp.zeros((n + 1,), ret.dtype).at[
        jnp.where(st.seg_start, st.seg_id, n)].set(base_rep)
    base = base_seg[st.seg_id]                        # per sorted op
    if op == "faa":
        fetched = base + st.loc_fetched
        success = jnp.ones((n,), bool)
    elif op in ("min", "max"):
        comb = jnp.minimum if op == "min" else jnp.maximum
        fetched = comb(base, st.loc_fetched)
        success = jnp.ones((n,), bool)
    elif op == "swp":
        fetched = jnp.where(st.seg_start, base, st.loc_fetched)
        success = jnp.ones((n,), bool)
    else:  # cas (uniform): the local chain assumed base == expected
        exp = jnp.asarray(expected, base.dtype)
        live = base == exp
        fetched = jnp.where(live, st.loc_fetched, base)
        success = live & st.loc_success
    valid = st.sidx < stage.m_global
    fetched = jnp.where(valid, fetched, jnp.zeros((), fetched.dtype))
    success = success & valid
    return fetched[st.inv], success[st.inv]


# ---------------------------------------------------------------------------
# Contention observatory (PR 10): stats from inside the combine passes
# ---------------------------------------------------------------------------

def _stage_level_counts(stages, m_global: int, all_axes: Tuple[str, ...]):
    """Per-exchange-level combining efficiency from the stage bookkeeping.

    Each `_Stage` already materializes the collision structure of its
    pre-combine (`comb.seg_start` marks group representatives, `comb.sidx`
    flags validity) — so ops-in / ops-out per level are free reductions over
    arrays the protocol computed anyway.  Every logical op lives on exactly
    one device at any level, so a psum over all participating axes counts
    each exactly once.
    """
    level_in, level_out = [], []
    for st_ in stages:
        v = st_.comb.sidx < m_global
        level_in.append(jax.lax.psum(v.sum(dtype=jnp.int32), all_axes))
        level_out.append(jax.lax.psum(
            (st_.comb.seg_start & v).sum(dtype=jnp.int32), all_axes))
    return level_in, level_out


def _contention_stats(gidx: Array, *, m_loc: int, m_global: int,
                      shard_axes: Tuple[str, ...],
                      rep_axes: Tuple[str, ...], level_in, level_out):
    """Mesh-global `ContentionStats` from per-device global slot ids.

    The occupancy reduction is the dense strategy's own psum_scatter pass
    run on unit values: each owner shard ends up holding the exact writer
    count for its rows, and the scalar observables reduce from there
    (replicated across the mesh, so shard_map out_specs use `P()`).
    """
    from repro.atomics import stats as _cstats

    occ = jnp.zeros((m_global + 1,), jnp.int32).at[gidx].add(1)[:-1]
    occ_own = jax.lax.psum_scatter(occ, shard_axes, scatter_dimension=0,
                                   tiled=True)
    if rep_axes:
        occ_own = jax.lax.psum(occ_own, rep_axes)
    all_axes = shard_axes + rep_axes
    n_ops = jax.lax.psum((gidx < m_global).sum(dtype=jnp.int32), all_axes)
    distinct = jax.lax.psum((occ_own > 0).sum(dtype=jnp.int32), shard_axes)
    max_occ = jax.lax.pmax(jnp.max(occ_own).astype(jnp.int32), shard_axes)
    hist = jax.lax.psum(_cstats.occupancy_hist(occ_own), shard_axes)
    # top-k: local candidates with global slot ids, re-ranked after a gather
    shard = jax.lax.axis_index(shard_axes).astype(jnp.int32)
    ids = shard * m_loc + jnp.arange(m_loc, dtype=jnp.int32)
    slots_l, counts_l = _cstats.topk_hot(occ_own, ids)
    slots_g = jax.lax.all_gather(slots_l, shard_axes, tiled=True)
    counts_g = jax.lax.all_gather(counts_l, shard_axes, tiled=True)
    slots_k, counts_k = _cstats.topk_hot(counts_g, slots_g)
    return _cstats.ContentionStats(
        n_ops=n_ops, distinct_slots=distinct, max_occupancy=max_occ,
        occupancy_hist=hist, topk_slots=slots_k, topk_counts=counts_k,
        level_ops_in=_cstats._level_array(level_in),
        level_ops_out=_cstats._level_array(level_out))


# ---------------------------------------------------------------------------
# The distributed executor
# ---------------------------------------------------------------------------

def execute_sharded(table: Array, indices: Array, values: Array, op: str,
                    expected: Optional[Array] = None, *, axis: AxisNames,
                    replica_axes: AxisNames = (), strategy: str = "auto",
                    backend: str = "auto",
                    spec: Optional[perf_model.HardwareSpec] = None,
                    axis_tiers: Optional[Sequence[Tier]] = None,
                    need_fetched: bool = True,
                    distinct_slots: Optional[int] = None,
                    reverse_ranks: bool = False,
                    collect_stats: bool = False):
    """Execute an RMW batch against a mesh-sharded table (inside shard_map).

    The distributed tier of the unified front-end — call it through
    `repro.atomics.execute`; this raw-array spelling is the internal entry.

    `table` is this device's shard (global slot ``g`` owned by shard
    ``g // m_local``, shards laid out major-to-minor over the ``axis``
    tuple); `indices` are global.  With `replica_axes`, the table is
    replicated over those axes (every replica holds the same shard) and
    writers on all replicas serialize replica-major; the updated shard is
    broadcast back so replicas stay identical.

    CAS accepts both expected forms: a scalar (uniform — pre-combinable,
    every strategy) or a per-op array, which cannot be pre-combined (the
    paper's "wasted work" case) and instead routes every op *un-combined*
    to its owner, which applies the serialized oracle over the received
    batch in device-rank order.  On that path ``strategy`` is ignored and
    ``backend`` must be "auto" or "serialized" (anything else raises, like
    the local tier).

    ``distinct_slots`` optionally feeds an observed distinct-slot estimate
    (e.g. the previous step's counts) to `select_exchange`, sharpening the
    one-shot-vs-hierarchical crossover for skewed batches; it never changes
    results, only the ``strategy="auto"`` choice.

    ``reverse_ranks`` flips the arrival-order contract to *descending*
    device rank (every exchange level processes sources in reverse): results
    then equal `rmw_serialized` on the batches concatenated in reverse
    device order.  Callers wanting a fully reversed global stream also
    reverse their local batch — see ``bfs_sharded(op="swp")``.

    Returns the PR-1 :class:`RmwResult` contract: results bit-identical to
    `rmw_serialized` on the device-rank-ordered concatenated batch (see
    module docstring), with `need_fetched=False` skipping the entire return
    path (fetched/success are zero placeholders).

    ``collect_stats=True`` (PR 10) additionally returns mesh-global
    :class:`repro.atomics.stats.ContentionStats` — the return becomes
    ``(RmwResult, ContentionStats)``.  Stats are read out of the combine
    passes' own bookkeeping (occupancy via the dense psum_scatter reduction,
    per-level efficiency from each `_Stage`'s seg_start flags), never change
    results, and stay device arrays (replicated: use `P()` out_specs).
    """
    if op not in OPS:
        raise ValueError(f"unknown op {op!r}")
    if op == "cas" and expected is None:
        raise ValueError("cas requires `expected`")
    if strategy not in STRATEGIES:
        raise ValueError(f"unknown strategy {strategy!r}; have {STRATEGIES}")

    shard_axes = _axes_tuple(axis)
    rep_axes = _axes_tuple(replica_axes) if replica_axes else ()
    sizes = [_axis_size(a) for a in shard_axes]
    n_shards = math.prod(sizes)
    n_rep = _axis_size(rep_axes) if rep_axes else 1
    m_loc = int(table.shape[0])
    m_global = m_loc * n_shards
    n = int(indices.shape[0])

    if op == "cas" and jnp.ndim(expected) != 0:
        # the owner resolve is a serialized-oracle pass by construction —
        # mirror the local tier's error instead of silently ignoring an
        # explicit non-oracle backend override
        if backend not in ("auto", "serialized"):
            raise ValueError(
                f"backend {backend!r} supports CAS only with a scalar "
                f"(uniform) `expected`; per-op expected arrays execute on "
                f"the serialized oracle at the owner shard")
        return _execute_cas_perop(
            table, indices, values, expected, shard_axes=shard_axes,
            rep_axes=rep_axes, n_shards=n_shards, n_rep=n_rep, m_loc=m_loc,
            m_global=m_global, need_fetched=need_fetched, spec=spec,
            reverse=reverse_ranks, collect_stats=collect_stats)

    if strategy == "auto":
        strategy = select_exchange(
            op, n, m_global, _mesh_axes(shard_axes, sizes, axis_tiers),
            spec=spec, need_fetched=need_fetched,
            uniform_expected=True, replicas=n_rep,
            distinct_slots=distinct_slots)
    if strategy == "hierarchical" and len(shard_axes) < 2:
        strategy = "oneshot"
    if strategy == "dense" and not (op == "faa" and not need_fetched):
        raise ValueError("strategy='dense' is the pure-FAA table-only path")
    # dense is pure commutative FAA — every arrival order yields the same
    # table, so reverse_ranks is trivially satisfied there.

    gidx = indices.astype(jnp.int32)
    gidx = jnp.where((gidx < 0) | (gidx >= m_global), m_global, gidx)
    zero_f = jnp.zeros((n,), values.dtype)
    zero_s = jnp.zeros((n,), bool)

    if strategy == "dense":
        dense = jnp.zeros((m_global + 1,), values.dtype
                          ).at[gidx].add(values)[:-1]
        delta = jax.lax.psum_scatter(dense, shard_axes, scatter_dimension=0,
                                     tiled=True)
        if rep_axes:
            delta = jax.lax.psum(delta, rep_axes)
        result = RmwResult(table + delta, zero_f, zero_s)
        if collect_stats:  # dense has no exchange levels: L = 0
            return result, _contention_stats(
                gidx, m_loc=m_loc, m_global=m_global, shard_axes=shard_axes,
                rep_axes=rep_axes, level_in=(), level_out=())
        return result

    # --- build the exchange pipeline (innermost level first) --------------
    stages = []
    cur_idx, cur_vals = gidx, values
    if strategy == "naive":
        # route every op individually: pre-combining disabled by giving each
        # op a unique routing key... simpler: one stage with cap = n and no
        # combining is emulated by tagging ops with their position so no two
        # share a group.  The owner still resolves in arrival order.
        cur_idx, cur_vals, stages = _push_naive(
            gidx, vals=values, op=op, expected=expected,
            axis=shard_axes, n_shards=n_shards, m_loc=m_loc,
            m_global=m_global, need_fetched=need_fetched,
            reverse=reverse_ranks)
    elif strategy == "oneshot" or len(shard_axes) == 1:
        dest = owner_shard(cur_idx, m_loc, n_shards)
        cap = min(n, m_loc)
        stage, cur_idx, cur_vals = _push(
            cur_idx, cur_vals, op, expected, axis=shard_axes,
            n_dest=n_shards, dest=dest, cap=cap, m_global=m_global,
            need_fetched=need_fetched, backend=backend, spec=spec,
            reverse=reverse_ranks)
        stages.append(stage)
    else:  # hierarchical: inner axes to the deputy, outer axis to the owner
        inner = shard_axes[1:]
        n_inner = math.prod(sizes[1:])
        n_outer = sizes[0]
        dest1 = owner_shard(cur_idx, m_loc, n_shards) % n_inner
        cap1 = min(n, m_loc * n_outer)
        stage, cur_idx, cur_vals = _push(
            cur_idx, cur_vals, op, expected, axis=inner, n_dest=n_inner,
            dest=dest1, cap=cap1, m_global=m_global,
            need_fetched=need_fetched, backend=backend, spec=spec,
            reverse=reverse_ranks)
        stages.append(stage)
        dest2 = owner_shard(cur_idx, m_loc * n_inner, n_outer)
        cap2 = min(n_inner * cap1, m_loc)
        stage, cur_idx, cur_vals = _push(
            cur_idx, cur_vals, op, expected, axis=shard_axes[0],
            n_dest=n_outer, dest=dest2, cap=cap2, m_global=m_global,
            need_fetched=need_fetched, backend=backend, spec=spec,
            reverse=reverse_ranks)
        stages.append(stage)

    if rep_axes:  # serialize replica groups at replica rank 0
        dest_r = jnp.zeros(cur_idx.shape, jnp.int32)
        cap_r = min(int(cur_idx.shape[0]), m_loc)
        stage, cur_idx, cur_vals = _push(
            cur_idx, cur_vals, op, expected, axis=rep_axes, n_dest=n_rep,
            dest=dest_r, cap=cap_r, m_global=m_global,
            need_fetched=need_fetched, backend=backend, spec=spec,
            reverse=reverse_ranks)
        stages.append(stage)

    # --- resolve at the owner ---------------------------------------------
    shard = jax.lax.axis_index(shard_axes)
    row = local_row(cur_idx, shard, m_loc, m_global)
    res = rmw_engine.execute_backend(
        table, row, cur_vals, op,
        None if op != "cas" else jnp.asarray(expected, table.dtype),
        backend=backend, spec=spec, need_fetched=need_fetched)
    new_table = res.table
    if rep_axes:
        # only replica rank 0 received real ops; broadcast its shard update
        new_table = table + jax.lax.psum(new_table - table, rep_axes)

    stats = None
    if collect_stats:
        level_in, level_out = _stage_level_counts(
            stages, m_global, shard_axes + rep_axes)
        stats = _contention_stats(
            gidx, m_loc=m_loc, m_global=m_global, shard_axes=shard_axes,
            rep_axes=rep_axes, level_in=level_in, level_out=level_out)

    if not need_fetched:
        result = RmwResult(new_table, zero_f, zero_s)
        return (result, stats) if collect_stats else result

    # --- unwind: bases flow back down the tree ----------------------------
    bases = res.fetched.astype(values.dtype)
    for stage in reversed(stages):
        bases, success = _pop(stage, bases, op, expected)
    result = RmwResult(new_table, bases, success)
    return (result, stats) if collect_stats else result


def _push_naive(gidx, vals, op, expected, axis, n_shards, m_loc, m_global,
                need_fetched, reverse=False):
    """The no-combining baseline: each op is its own routed group.

    Packing is by per-destination arrival rank over *all* ops (cap = n), so
    the owner sees every individual op in source-rank-then-local order —
    the serialized ping-pong regime the paper measures (one line-ownership
    transfer per op), which the benchmark uses as the contention baseline.
    """
    n = gidx.shape[0]
    dest = owner_shard(gidx, m_loc, n_shards)
    valid = gidx < m_global
    cap = n
    scratch = n_shards * cap
    slotpos = _rank_slotpos(dest, valid, n_shards, cap)
    send_idx = _scatter_padded(m_global, jnp.int32, slotpos, gidx, scratch)
    send_val = _scatter_padded(0, vals.dtype, slotpos, vals, scratch)
    recv_idx, recv_val = _route_pair(send_idx, send_val, axis, n_shards, cap)
    if reverse:
        recv_idx = _flip_lanes(recv_idx, n_shards, cap)
        recv_val = _flip_lanes(recv_val, n_shards, cap)
    comb = _Combined(order=jnp.arange(n), inv=jnp.arange(n), sidx=gidx,
                     sval=vals, seg_start=jnp.ones((n,), bool),
                     seg_id=jnp.arange(n, dtype=jnp.int32),
                     combined=vals,
                     loc_fetched=jnp.full((n,), _identity_base(
                         op, vals.dtype, expected), vals.dtype),
                     loc_success=jnp.ones((n,), bool))
    stage = _Stage(axis=axis, n_dest=n_shards, cap=cap, comb=comb,
                   slotpos=slotpos, m_global=m_global, reverse=reverse)
    return recv_idx, recv_val, [stage]


# ---------------------------------------------------------------------------
# Per-op-expected CAS: owner-side oracle pass over un-combined ops
# ---------------------------------------------------------------------------

def _route_flat(buf: Array, axis: AxisNames, n_dest: int, cap: int) -> Array:
    """One padded all_to_all of a flat (n_dest * cap,) payload buffer."""
    return jax.lax.all_to_all(buf.reshape(n_dest, cap), axis, split_axis=0,
                              concat_axis=0).reshape(-1)


def _route_cols(cols, axis: AxisNames, n_dest: int, cap: int):
    """Move several same-length payload columns over one exchange.

    4-byte columns ride together as one bitcast-packed (n_dest, cap, k)
    buffer — ONE all_to_all launch total, the same single-launch pricing
    `_route_pair` gets for its (id, value) rows; any wider dtype falls back
    to one collective per column."""
    if all(c.dtype.itemsize == 4 for c in cols):
        bits = [jax.lax.bitcast_convert_type(c, jnp.int32) for c in cols]
        packed = jnp.stack(bits, axis=-1).reshape(n_dest, cap, len(cols))
        recv = jax.lax.all_to_all(packed, axis, split_axis=0,
                                  concat_axis=0).reshape(-1, len(cols))
        return tuple(jax.lax.bitcast_convert_type(recv[:, j], c.dtype)
                     for j, c in enumerate(cols))
    return tuple(_route_flat(c, axis, n_dest, cap) for c in cols)


def _push_uncombined(gidx: Array, vals: Array, exps: Array, *,
                     axis: AxisNames, n_dest: int, dest: Array,
                     m_global: int, reverse: bool = False):
    """Route (slot id, value, expected) rows with NO pre-combining.

    Like `_push_naive`, packing is by per-destination arrival rank over all
    valid ops (cap = n, the un-combinable worst case), so the receiver sees
    every individual op in source-rank-then-local order — exactly the
    arrival-order contract.  Returns (slotpos, recv_idx, recv_val, recv_exp).
    """
    n = gidx.shape[0]
    valid = gidx < m_global
    cap = n
    slotpos = _rank_slotpos(dest, valid, n_dest, cap)
    scratch = n_dest * cap
    send_idx = _scatter_padded(m_global, jnp.int32, slotpos, gidx, scratch)
    send_val = _scatter_padded(0, vals.dtype, slotpos, vals, scratch)
    send_exp = _scatter_padded(0, exps.dtype, slotpos, exps, scratch)
    recv_idx, recv_val, recv_exp = _route_cols(
        (send_idx, send_val, send_exp), axis, n_dest, cap)
    if reverse:
        recv_idx, recv_val, recv_exp = (
            _flip_lanes(c, n_dest, cap)
            for c in (recv_idx, recv_val, recv_exp))
    return slotpos, recv_idx, recv_val, recv_exp


def _execute_cas_perop(table: Array, indices: Array, values: Array,
                       expected: Array, *, shard_axes: Tuple[str, ...],
                       rep_axes: Tuple[str, ...], n_shards: int, n_rep: int,
                       m_loc: int, m_global: int, need_fetched: bool,
                       spec, reverse: bool = False,
                       collect_stats: bool = False):
    """Cross-shard CAS with per-op expected values (ROADMAP closure).

    Per-op expected CAS chains do not compose associatively (the combined
    effect of a group depends on each op's own expected value), so nothing
    can be pre-combined — the paper's "wasted work" regime.  Instead every
    op is routed raw to its owner shard (`_push_uncombined`, replica stage
    included), which applies the **serialized oracle** — the only
    general-CAS backend — over the received batch in device-rank order.
    The owner's per-op fetched values ARE the final fetched values (no
    local chain to recombine); success is recomputed at the source as
    ``fetched == expected``.  Results are bit-identical to `rmw_serialized`
    on the device-rank-ordered concatenated batch, same as every other op.
    """
    n = int(indices.shape[0])
    gidx = indices.astype(jnp.int32)
    gidx = jnp.where((gidx < 0) | (gidx >= m_global), m_global, gidx)
    exp = jnp.asarray(expected, table.dtype)

    stages = []                     # (axis, n_dest, cap, slotpos)
    cur_idx, cur_val, cur_exp = gidx, values, exp
    dest = owner_shard(cur_idx, m_loc, n_shards)
    slotpos, cur_idx, cur_val, cur_exp = _push_uncombined(
        cur_idx, cur_val, cur_exp, axis=shard_axes, n_dest=n_shards,
        dest=dest, m_global=m_global, reverse=reverse)
    stages.append((shard_axes, n_shards, n, slotpos))
    if rep_axes:                    # serialize replica groups at rank 0
        n2 = int(cur_idx.shape[0])
        dest_r = jnp.zeros((n2,), jnp.int32)
        slotpos, cur_idx, cur_val, cur_exp = _push_uncombined(
            cur_idx, cur_val, cur_exp, axis=rep_axes, n_dest=n_rep,
            dest=dest_r, m_global=m_global, reverse=reverse)
        stages.append((rep_axes, n_rep, n2, slotpos))

    shard = jax.lax.axis_index(shard_axes)
    row = local_row(cur_idx, shard, m_loc, m_global)
    res = rmw_engine.execute_backend(table, row, cur_val, "cas", cur_exp,
                                     backend="serialized", spec=spec,
                                     need_fetched=need_fetched)
    new_table = res.table
    if rep_axes:                    # broadcast replica rank 0's update
        new_table = table + jax.lax.psum(new_table - table, rep_axes)

    stats = None
    if collect_stats:
        # un-combinable by construction: every level moves each op raw, so
        # ops-in == ops-out at every level (the measured "wasted work").
        all_axes = shard_axes + rep_axes
        n_valid = jax.lax.psum((gidx < m_global).sum(dtype=jnp.int32),
                               all_axes)
        levels = [n_valid] * len(stages)
        stats = _contention_stats(
            gidx, m_loc=m_loc, m_global=m_global, shard_axes=shard_axes,
            rep_axes=rep_axes, level_in=levels, level_out=levels)

    zero_f = jnp.zeros((n,), values.dtype)
    zero_s = jnp.zeros((n,), bool)
    if not need_fetched:
        result = RmwResult(new_table, zero_f, zero_s)
        return (result, stats) if collect_stats else result

    bases = res.fetched.astype(values.dtype)
    for axis, n_dest, cap, slotpos in reversed(stages):
        if reverse:                 # undo the receive-side flip per level
            bases = _flip_lanes(bases, n_dest, cap)
        ret = _route_flat(bases, axis, n_dest, cap)
        ret = jnp.concatenate([ret, jnp.zeros((1,), ret.dtype)])
        bases = ret[slotpos]        # scratch -> 0
    valid = gidx < m_global
    fetched = jnp.where(valid, bases, zero_f)
    success = valid & (bases == exp.astype(values.dtype))
    result = RmwResult(new_table, fetched, success)
    return (result, stats) if collect_stats else result


# ---------------------------------------------------------------------------
# Cost model: the distributed tier of the paper's L(A, S) decision procedure
# ---------------------------------------------------------------------------

def _mesh_axes(names: Sequence[str], sizes: Sequence[int],
               tiers: Optional[Sequence[Tier]]) -> Tuple[MeshAxis, ...]:
    """Default topology: outermost axis crosses pods (DCN) when there is more
    than one level; everything else rides the ICI torus."""
    if tiers is None:
        tiers = [Tier.DCN_REMOTE_POD if (i == 0 and len(names) > 1)
                 else Tier.ICI_NEIGHBOR for i in range(len(names))]
    return tuple(MeshAxis(name=n, size=s, tier=t)
                 for n, s, t in zip(names, sizes, tiers))


def _cost_engine(spec, op: str, n: int, m: int, need_fetched: bool) -> float:
    """Cheapest local-backend prediction — phase-1/phase-2 engine passes."""
    cands = [b for b in rmw_engine.BACKENDS.values()
             if b.supports(op, uniform_expected=True, m=m,
                           need_fetched=need_fetched)]
    return min(b.cost(spec, op, max(n, 1), max(m, 1), need_fetched)
               for b in cands)


def _level_sharing(axes: Sequence[MeshAxis], i: int, senders: int) -> int:
    """Concurrent senders squeezing through one link of level ``i``.

    ICI torus links are per-device (no sharing); the DCN uplink is one pipe
    per pod, shared by every in-pod device participating in the exchange —
    the inner axes' sizes (times any extra ``senders`` the caller knows
    about, e.g. deputies at a hierarchical outer level)."""
    if axes[i].tier is not Tier.DCN_REMOTE_POD:
        return 1
    return senders * math.prod(a.size for a in axes[i + 1:])


def _a2a_s(spec, nbytes: int, axes: Sequence[MeshAxis],
           senders: int = 1) -> float:
    """One padded all_to_all over (possibly flattened) axes.

    A flattened a2a decomposes into one transpose step per mesh axis, each
    carrying the full per-device payload (no combining between steps, so the
    payload does not shrink — that is exactly what the hierarchical strategy
    adds).  One software launch total; DCN levels pay the shared-uplink
    penalty of :func:`_level_sharing`.
    """
    t = spec.collective_launch_s
    for i, ax in enumerate(axes):
        if ax.size > 1:
            t += collective_model.collective_time_s(
                spec, "all_to_all", nbytes * _level_sharing(axes, i, senders),
                ax)
    return t


def _rs_s(spec, nbytes: int, axes: Sequence[MeshAxis]) -> float:
    """Hierarchical reduce_scatter over flattened axes: the inner level
    carries the full payload, each outer level 1/size of the previous."""
    t = spec.collective_launch_s
    share = float(nbytes)
    for i in reversed(range(len(axes))):  # inner (fast) first
        ax = axes[i]
        if ax.size > 1:
            t += collective_model.collective_time_s(
                spec, "reduce_scatter",
                int(share) * _level_sharing(axes, i, 1), ax)
            share /= ax.size
    return t


def _cap_hint(cap: int, distinct_slots: Optional[int]) -> int:
    """Tighten a worst-case exchange cap with an observed distinct-slot
    estimate (the dynamic contention hint): after pre-combining, at most one
    row per distinct slot survives, so the *expected* payload is bounded by
    the estimate even though the padded worst-case buffer is not.  Selection
    only — the executor's real caps stay worst-case correct."""
    if distinct_slots is None:
        return cap
    return max(1, min(cap, int(distinct_slots)))


def cost_exchange_oneshot(spec, op: str, n: int, m_global: int,
                          axes: Sequence[MeshAxis],
                          need_fetched: bool = True,
                          distinct_slots: Optional[int] = None) -> float:
    n_shards = math.prod(a.size for a in axes)
    m_loc = max(1, m_global // n_shards)
    cap = _cap_hint(min(n, m_loc), distinct_slots)
    t = _cost_engine(spec, op, n, n, need_fetched)           # pre-combine
    t += _a2a_s(spec, n_shards * cap * ROW_BYTES, axes)      # route
    t += _cost_engine(spec, op, n_shards * cap, m_loc, need_fetched)
    if need_fetched:
        t += _a2a_s(spec, n_shards * cap * 4, axes)          # bases back
        t += 3 * n * (spec.gather_elem_s or 2e-9)            # reconstruct
    return t


def cost_exchange_hierarchical(spec, op: str, n: int, m_global: int,
                               axes: Sequence[MeshAxis],
                               need_fetched: bool = True,
                               distinct_slots: Optional[int] = None) -> float:
    if len(axes) < 2:
        return float("inf")
    n_shards = math.prod(a.size for a in axes)
    n_outer = axes[0].size
    n_inner = n_shards // n_outer
    m_loc = max(1, m_global // n_shards)
    cap1 = _cap_hint(min(n, m_loc * n_outer), distinct_slots)
    cap2 = _cap_hint(min(n_inner * cap1, m_loc), distinct_slots)
    t = _cost_engine(spec, op, n, n, need_fetched)           # pre-combine
    t += _a2a_s(spec, n_inner * cap1 * ROW_BYTES, axes[1:])  # ICI to deputy
    t += _cost_engine(spec, op, n_inner * cap1, n_inner * cap1, need_fetched)
    t += _a2a_s(spec, n_outer * cap2 * ROW_BYTES, axes[:1],  # DCN to owner
                senders=n_inner)
    t += _cost_engine(spec, op, n_outer * cap2, m_loc, need_fetched)
    if need_fetched:
        t += _a2a_s(spec, n_outer * cap2 * 4, axes[:1], senders=n_inner)
        t += _a2a_s(spec, n_inner * cap1 * 4, axes[1:])
        t += 3 * (n + n_inner * cap1) * (spec.gather_elem_s or 2e-9)
    return t


def cost_exchange_naive(spec, op: str, n: int, m_global: int,
                        axes: Sequence[MeshAxis],
                        need_fetched: bool = True,
                        distinct_slots: Optional[int] = None) -> float:
    del distinct_slots              # no combining: every op ships regardless
    n_shards = math.prod(a.size for a in axes)
    m_loc = max(1, m_global // n_shards)
    t = _a2a_s(spec, n_shards * n * ROW_BYTES, axes)
    t += _cost_engine(spec, op, n_shards * n, m_loc, need_fetched)
    if need_fetched:
        t += _a2a_s(spec, n_shards * n * 4, axes)
    return t


def cost_exchange_dense(spec, op: str, n: int, m_global: int,
                        axes: Sequence[MeshAxis],
                        need_fetched: bool = True,
                        distinct_slots: Optional[int] = None) -> float:
    del distinct_slots              # dense path always moves the full table
    if op != "faa" or need_fetched:
        return float("inf")
    gather = spec.gather_elem_s or 2e-9
    return (n + m_global) * gather + _rs_s(spec, 4 * m_global, axes)


EXCHANGE_COSTS = {
    "oneshot": cost_exchange_oneshot,
    "hierarchical": cost_exchange_hierarchical,
    "naive": cost_exchange_naive,
    "dense": cost_exchange_dense,
}


def select_exchange(op: str, n: int, m_global: int,
                    axes: Sequence[MeshAxis], *,
                    spec: Optional[perf_model.HardwareSpec] = None,
                    need_fetched: bool = True, uniform_expected: bool = True,
                    replicas: int = 1, include_naive: bool = False,
                    distinct_slots: Optional[int] = None) -> str:
    """Cheapest distributed strategy for (op, n/device, table, topology).

    This is `select_backend`'s distributed tier: the same HardwareSpec
    constants, extended with the ICI/DCN exchange terms, decide one-shot vs
    hierarchical (per-pod then cross-pod) combining — the paper's Fig. 8
    crossover as a decision procedure.  `naive` (the measured per-op
    baseline) is priced in `EXCHANGE_COSTS` but excluded from auto selection
    unless `include_naive`: its padded exchange buffer is ``n_shards * n``
    rows, which is memory-hostile even in the cells where skipping the
    pre-combine pass would nominally win.

    ``distinct_slots`` is the **dynamic contention hint** (ROADMAP): an
    observed estimate of how many distinct slots the batch touches (e.g.
    the previous step's counts).  The static costs assume the worst-case
    exchange caps (bounded only by batch and table size); a skewed batch
    that actually touches few slots pre-combines to almost nothing, where
    the hierarchy's extra level of launches and engine passes no longer
    pays for its DCN savings — the hint shifts that crossover.  Selection
    only: results never depend on it.
    """
    return select_exchange_with_cost(
        op, n, m_global, axes, spec=spec, need_fetched=need_fetched,
        uniform_expected=uniform_expected, replicas=replicas,
        include_naive=include_naive, distinct_slots=distinct_slots).choice


def select_exchange_with_cost(op: str, n: int, m_global: int,
                              axes: Sequence[MeshAxis], *,
                              spec: Optional[perf_model.HardwareSpec] = None,
                              need_fetched: bool = True,
                              uniform_expected: bool = True,
                              replicas: int = 1,
                              include_naive: bool = False,
                              distinct_slots: Optional[int] = None
                              ) -> rmw_engine.Selection:
    """`select_exchange` returning the full predicted-cost record
    (`rmw_engine.Selection`) — persisted by the telemetry decision events
    so the exchange tier's drift is trackable per strategy."""
    if op not in OPS:
        raise ValueError(f"unknown op {op!r}")
    if op == "cas" and not uniform_expected:
        raise ValueError(
            "select_exchange prices pre-combined exchanges; per-op expected "
            "CAS always executes on the un-combined owner-oracle path")
    spec = spec or rmw_engine.default_spec()
    del replicas  # the replica stage cost is identical across strategies
    costs = {name: fn(spec, op, n, m_global, axes, need_fetched,
                      distinct_slots=distinct_slots)
             for name, fn in EXCHANGE_COSTS.items()
             if name != "naive" or include_naive}
    best = min(costs, key=costs.get)   # ties: EXCHANGE_COSTS order, as ever
    return rmw_engine.Selection(best, costs[best], costs)
