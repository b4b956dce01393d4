"""`execute`: one entry point over every RMW execution tier.

Dispatch ladder (all decisions at trace time — shapes are static under jit):

1. **Tier** — an :class:`~repro.atomics.table.AtomicTable` with mesh axes
   (``table.axis``) executing *inside* ``shard_map`` routes to the sharded
   subsystem (`core.rmw_sharded`); a local table routes to the engine
   registry (`core.rmw_engine`).  A sharded table used outside ``shard_map``
   is an error (the collectives need bound axis names), caught with a
   guidance message instead of a cryptic NameError.
2. **Strategy/backend** — within the tier, the cost models pick the
   implementation: `select_backend` over the engine registry (serialized /
   sort / one-hot / Pallas), `select_exchange` over the exchange strategies
   (one-shot / hierarchical / dense), both overridable via the ``backend=``
   and ``strategy=`` keywords.  ``distinct_slots`` feeds the exchange
   selector's dynamic contention hint (an observed distinct-slot estimate)
   to sharpen the one-shot-vs-hierarchical crossover for skewed batches —
   estimator-backed when a `repro.tuning.SpecController` is active (the
   retry combinator's collision counts feed an EWMA per call site), with
   the explicit keyword remaining an optional caller override.
3. **Semantics** — per-op-expected CAS (non-uniform `Cas`) runs on the
   serialized oracle locally, and across shards via the owner-side oracle
   pass over un-combined ops (see `core.rmw_sharded`).

Every path returns results bit-identical to `core.rmw.rmw_serialized` on
the same batch (sharded: on the device-rank-ordered concatenation — the
arrival-order contract).
"""

from __future__ import annotations

import functools
import time
from typing import Any, NamedTuple, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

from repro import telemetry
from repro.telemetry import core as _tcore
from repro.atomics import contracts as _contracts
from repro.atomics import stats as _cstats
from repro.atomics.ops import AtomicOp
from repro.atomics.table import AtomicTable
from repro.core import rmw as rmw_mod
from repro.core import rmw_engine

Array = jax.Array


class AtomicResult(NamedTuple):
    """Result of `execute`: the updated table handle + per-op outputs.

    ``fetched[i]`` is the value op ``i`` observed *before* executing
    (serialized order), ``success[i]`` its CAS outcome (always True for
    non-CAS ops).  With ``need_fetched=False`` both are zero placeholders —
    only ``table`` is meaningful.  When `execute` was given a *sequence* of
    op batches, ``fetched``/``success`` are tuples, one entry per batch.

    ``stats`` is ``None`` unless the call passed ``collect_stats=True``, in
    which case it holds the batch's device-side
    :class:`~repro.atomics.stats.ContentionStats` (a tuple of them for a
    sequence of op batches).
    """

    table: AtomicTable
    fetched: Any
    success: Any
    stats: Any = None


def _axis_names(table: AtomicTable) -> Tuple[str, ...]:
    names: Tuple[str, ...] = ()
    for group in (table.axis, table.replica_axes):
        if group:
            names += (group,) if isinstance(group, str) else tuple(group)
    return names


def _axes_bound(names: Tuple[str, ...]) -> bool:
    """True iff every mesh axis name is bound in the current trace — i.e.
    we are inside a ``shard_map`` (or pmap) that carries those axes."""
    try:
        for name in names:
            jax.lax.axis_index(name)
        return True
    except NameError:
        return False


@functools.partial(jax.jit, static_argnames=("op", "backend", "need_fetched"))
def _local_exec_stats(table: Array, indices: Array, values: Array, expected,
                      *, op: str, backend: str, need_fetched: bool):
    """Local execution + contention stats as ONE compiled program.

    The stats path must not add a second eager dispatch (on CPU that alone
    costs more than the gate allows), so the backend pass and the occupancy
    reduction compile together; `backend` arrives pre-resolved (static) so
    no spec object needs to cross the jit boundary.  Results are the same
    ops the eager path runs — bit-identity is asserted in tests and gated
    in benchmarks/contention_observe.py.
    """
    res = rmw_engine.execute_backend(table, indices, values, op, expected,
                                     backend=backend,
                                     need_fetched=need_fetched)
    m = table.shape[0]
    if backend == "pallas":
        # the kernel's counters output ref — same one-hot contraction the
        # Mosaic combine runs, emitted instead of discarded
        from repro.kernels.rmw import ops as _kops
        occ = _kops.slot_occupancy(indices, m)
    else:
        occ = rmw_engine.slot_occupancy(indices, m)
    idx = indices.astype(jnp.int32)
    n_ops = ((idx >= 0) & (idx < m)).sum(dtype=jnp.int32)
    return res, _cstats.stats_from_occupancy(occ, n_ops)


def _dispatch_one(table: AtomicTable, op: AtomicOp, *, need_fetched: bool,
                  backend: str, strategy: str, spec,
                  distinct_slots: Optional[int], reverse_ranks: bool,
                  collect_stats: bool = False):
    if not isinstance(op, AtomicOp):
        raise TypeError(
            f"ops must be atomics.Faa/Swp/Min/Max/Cas instances, "
            f"got {type(op).__name__}")
    stats = None
    if table.is_sharded:
        if not _axes_bound(_axis_names(table)):
            raise ValueError(
                f"AtomicTable is sharded over mesh axes {table.axis!r} but "
                f"execute() was called outside shard_map — wrap the call in "
                f"repro.sharding.shard_map_compat over those axes (the "
                f"sharded tier uses collectives), or build a local table")
        # deferred: core.rmw_sharded imports repro.atomics.layout at module
        # scope, so binding it here keeps the package import acyclic
        from repro.core.rmw_sharded import execute_sharded
        res = execute_sharded(
            table.data, op.indices, op.values, op.kind, op.expected,
            axis=table.axis, replica_axes=table.replica_axes,
            strategy=strategy, backend=backend, spec=spec,
            need_fetched=need_fetched, distinct_slots=distinct_slots,
            reverse_ranks=reverse_ranks, collect_stats=collect_stats)
        if collect_stats:
            res, stats = res
    else:
        if reverse_ranks:
            # on one device the caller owns the whole order: reversing is
            # just op[::-1].  Accepting the flag here would imply a
            # cross-device contract that does not exist on this tier.
            raise ValueError(
                "reverse_ranks reverses the device-rank arrival order of "
                "the sharded tier; for a local table reverse the batch "
                "itself (indices[::-1], values[::-1])")
        if strategy != "auto" or distinct_slots is not None:
            # exchange strategies/hints only exist on the sharded tier: a
            # caller naming one against a local table almost certainly
            # migrated an rmw_sharded call but forgot AtomicTable(axis=...)
            # — running locally would silently skip the exchange (global
            # indices past the local shard would just vanish as OOR drops).
            raise ValueError(
                f"strategy={strategy!r} / distinct_slots apply to the "
                f"sharded tier only, but the table is local — wrap it as "
                f"AtomicTable(data, axis=...) (and call inside shard_map) "
                f"or drop the sharded-tier arguments")
        if collect_stats:
            resolved = backend
            if resolved == "auto":
                resolved = rmw_engine.select_backend(
                    op.kind, int(op.indices.shape[0]),
                    int(table.data.shape[0]), spec,
                    uniform_expected=(op.kind != "cas")
                    or rmw_engine._is_uniform_expected(op.expected),
                    dtype=table.dtype, need_fetched=need_fetched)
            res, stats = _local_exec_stats(
                table.data, op.indices, op.values, op.expected,
                op=op.kind, backend=resolved, need_fetched=need_fetched)
        else:
            res = rmw_engine.execute_backend(
                table.data, op.indices, op.values, op.kind, op.expected,
                backend=backend, spec=spec, need_fetched=need_fetched)
    return table.with_data(res.table), res.fetched, res.success, stats


# ---------------------------------------------------------------------------
# Telemetry: one decision event per executed op batch
# ---------------------------------------------------------------------------


def _decision_fields(table: AtomicTable, op: AtomicOp, *, need_fetched: bool,
                     backend: str, strategy: str, spec,
                     distinct_slots: Optional[int]) -> dict:
    """Mirror the dispatch ladder's selection (same deterministic inputs ->
    same choice) into one flat event record: tier, choice, and the
    selector's predicted cost — the prediction half of the drift tracker.
    Never raises: a selection that cannot be priced records ``None``."""
    n = int(op.indices.shape[0])
    perop_cas = op.kind == "cas" and op.expected is not None \
        and jnp.ndim(op.expected) != 0
    fields = dict(op=op.kind, n=n, need_fetched=need_fetched,
                  distinct_slots=distinct_slots)
    try:
        if table.is_sharded:
            from repro.core import rmw_sharded as rs
            shard_axes = rs._axes_tuple(table.axis)
            sizes = [rs._axis_size(a) for a in shard_axes]
            m_global = int(table.data.shape[0]) * _prod(sizes)
            fields.update(tier="sharded", m=m_global,
                          n_shards=_prod(sizes), backend=backend)
            if perop_cas:
                # un-combined owner-oracle path: strategy does not apply
                # and the exchange cost model declines to price it
                fields.update(strategy="perop_oracle", predicted_s=None)
            elif strategy == "auto":
                n_rep = rs._axis_size(table.replica_axes) \
                    if table.replica_axes else 1
                sel = rs.select_exchange_with_cost(
                    op.kind, n, m_global,
                    rs._mesh_axes(shard_axes, sizes, None), spec=spec,
                    need_fetched=need_fetched, uniform_expected=True,
                    replicas=n_rep, distinct_slots=distinct_slots)
                fields.update(strategy=sel.choice,
                              predicted_s=sel.predicted_s)
            else:
                used = strategy
                if strategy == "hierarchical" and len(shard_axes) < 2:
                    used = "oneshot"    # the executor's documented demotion
                fields.update(strategy=used, predicted_s=rs.EXCHANGE_COSTS[
                    used](spec or rmw_engine.default_spec(), op.kind, n,
                          m_global, rs._mesh_axes(shard_axes, sizes, None),
                          need_fetched, distinct_slots=distinct_slots))
        else:
            m = int(table.data.shape[0])
            fields.update(tier="local", m=m, strategy=None)
            uniform = not perop_cas
            if backend == "auto":
                sel = rmw_engine.select_backend_with_cost(
                    op.kind, n, m, spec, uniform_expected=uniform,
                    dtype=table.dtype, need_fetched=need_fetched)
                fields.update(backend=sel.choice, predicted_s=sel.predicted_s)
            else:
                b = rmw_engine.BACKENDS.get(backend)
                fields.update(backend=backend, predicted_s=(
                    b.cost(spec or rmw_engine.default_spec(), op.kind, n, m,
                           need_fetched) if b is not None else None))
    except Exception:  # noqa: BLE001 — observability must not break dispatch
        fields.setdefault("tier", "sharded" if table.is_sharded else "local")
        fields.setdefault("predicted_s", None)
    return fields


def _prod(xs) -> int:
    out = 1
    for x in xs:
        out *= int(x)
    return out


#: decision fields are a pure function of (kind, n, m, backend, ...) — on
#: the local tier the same shapes recur every step, so the eager hot path
#: pays one dict lookup instead of re-running the cost model per call (the
#: <5% instrumentation-overhead budget).  Sharded fields stay uncached:
#: they are computed at trace time only, and axis sizes are trace-scoped.
_DECISION_CACHE: dict = {}
_DECISION_CACHE_MAX = 1024


def _execute_one(table: AtomicTable, op: AtomicOp, *, need_fetched: bool,
                 backend: str, strategy: str, spec,
                 distinct_slots: Optional[int], reverse_ranks: bool,
                 collect_stats: bool = False):
    if _contracts._observer is not None:
        # static analysis in progress: report this call site's contract
        # BEFORE dispatch (a sharded-outside-shard_map call raises below,
        # and the analyzer turns the recorded site into the finding), and
        # route the op's operands through the identity marker primitive so
        # the rule engine finds them in the final jaxpr — dispatch then
        # proceeds on the marked (semantically identical) copy
        sid = _contracts.next_site()
        roles = ("op_indices", "op_values", "op_expected")
        children, aux = op.tree_flatten()
        op = type(op).tree_unflatten(aux, tuple(
            _contracts.mark(c, role=r, kind=op.kind, site=sid)
            for c, r in zip(children, roles)))
        _contracts.notify(
            "execute", table=table, op=op, site_id=sid,
            need_fetched=need_fetched, backend=backend, strategy=strategy,
            distinct_slots=distinct_slots, reverse_ranks=reverse_ranks,
            axes_bound=(not table.is_sharded)
            or _axes_bound(_axis_names(table)))
    if not telemetry.enabled():
        return _dispatch_one(table, op, need_fetched=need_fetched,
                             backend=backend, strategy=strategy, spec=spec,
                             distinct_slots=distinct_slots,
                             reverse_ranks=reverse_ranks,
                             collect_stats=collect_stats)
    if not isinstance(op, AtomicOp) or \
            (table.is_sharded and not _axes_bound(_axis_names(table))):
        # let the dispatcher raise its guidance errors un-instrumented
        return _dispatch_one(table, op, need_fetched=need_fetched,
                             backend=backend, strategy=strategy, spec=spec,
                             distinct_slots=distinct_slots,
                             reverse_ranks=reverse_ranks,
                             collect_stats=collect_stats)
    data = table.data
    if table.is_sharded:
        # trace-time only (axis sizes are trace-scoped): never cached, and
        # the one-per-compilation cost is invisible
        fields = _decision_fields(table, op, need_fetched=need_fetched,
                                  backend=backend, strategy=strategy,
                                  spec=spec, distinct_slots=distinct_slots)
        fields["event"] = "atomics.execute"
    else:
        # inlined cache lookup — on the eager hot path the function-call
        # and kwargs overhead of a helper is itself a measurable slice of
        # the <5% instrumentation budget.  NB the raw dtype object in the
        # key: hashable, where str(dtype) costs ~10us/call.
        perop = op.kind == "cas" and op.expected is not None \
            and jnp.ndim(op.expected) != 0
        key = (op.kind, op.indices.shape[0], data.shape[0], backend,
               strategy, need_fetched, perop, id(spec), distinct_slots,
               data.dtype, rmw_engine._SPEC_EPOCH)
        fields = _DECISION_CACHE.get(key)
        if fields is None:
            fields = _decision_fields(
                table, op, need_fetched=need_fetched, backend=backend,
                strategy=strategy, spec=spec, distinct_slots=distinct_slots)
            fields["event"] = "atomics.execute"   # pre-stamped template
            if len(_DECISION_CACHE) >= _DECISION_CACHE_MAX:
                _DECISION_CACHE.clear()
            _DECISION_CACHE[key] = fields
        fields = dict(fields)        # the cached template stays pristine
    traced = isinstance(data, jax.core.Tracer) \
        or isinstance(op.indices, jax.core.Tracer)
    # _tcore flag reads instead of the telemetry.*_enabled() accessors:
    # each saved call is ~0.15us against the overhead budget
    if traced or not _tcore._sync:
        if _tcore._annotate and not traced:
            with telemetry.annotation(
                    f"atomics.execute/{fields.get('tier')}"):
                out = _dispatch_one(table, op, need_fetched=need_fetched,
                                    backend=backend, strategy=strategy,
                                    spec=spec, distinct_slots=distinct_slots,
                                    reverse_ranks=reverse_ranks,
                                    collect_stats=collect_stats)
        else:
            out = _dispatch_one(table, op, need_fetched=need_fetched,
                                backend=backend, strategy=strategy,
                                spec=spec, distinct_slots=distinct_slots,
                                reverse_ranks=reverse_ranks,
                                collect_stats=collect_stats)
    else:
        t0 = time.perf_counter()
        if _tcore._annotate:
            with telemetry.annotation(
                    f"atomics.execute/{fields.get('tier')}"):
                out = _dispatch_one(table, op, need_fetched=need_fetched,
                                    backend=backend, strategy=strategy,
                                    spec=spec, distinct_slots=distinct_slots,
                                    reverse_ranks=reverse_ranks,
                                    collect_stats=collect_stats)
        else:
            out = _dispatch_one(table, op, need_fetched=need_fetched,
                                backend=backend, strategy=strategy,
                                spec=spec, distinct_slots=distinct_slots,
                                reverse_ranks=reverse_ranks,
                                collect_stats=collect_stats)
        sync = (out[0].data, out[1], out[2])
        if out[3] is not None:
            sync += (out[3],)
        jax.block_until_ready(sync)
        fields["measured_s"] = time.perf_counter() - t0
    # the cache-copy dict becomes the event itself (record_event skips the
    # kwargs rebuild that `record` pays — this is the hottest record site)
    fields["traced"] = traced
    telemetry.record_event(fields)
    if out[3] is not None and not traced and _tcore._sync:
        # PR-7 jit discipline: contention.* events only at sync boundaries —
        # the eager sync branch above already blocked on the stats leaves,
        # so the host readout below costs no extra device round trip.
        telemetry.record_event(_cstats.stats_to_fields(
            out[3], tier=fields.get("tier"), op=op.kind,
            n=fields.get("n"), m=fields.get("m"), traced=False))
    return out


def execute(table: Union[AtomicTable, Array],
            ops: Union[AtomicOp, Sequence[AtomicOp]], *,
            need_fetched: bool = True, backend: str = "auto",
            strategy: str = "auto", spec=None,
            distinct_slots: Optional[int] = None,
            reverse_ranks: bool = False,
            collect_stats: bool = False) -> AtomicResult:
    """Execute typed RMW op batches against a table, cost-model-routed.

    Args:
      table: an :class:`AtomicTable` (or a bare 1-D array, treated as a
        local table).  Inside ``shard_map``, a sharded table's ``data`` is
        the local shard and ``indices`` are *global* slot ids.
      ops: one op batch (``atomics.Faa(idx, vals)`` ...) or a sequence,
        applied in order against the running table.
      need_fetched: False lets backends skip the per-op fetch machinery
        (table-only fast paths); ``fetched``/``success`` are then zeros.
      backend: engine backend for local execution and the pre-combine /
        resolve passes of the sharded tier ("auto" = `select_backend`).
      strategy: exchange strategy for the sharded tier ("auto" =
        `select_exchange`); ignored for local tables.
      spec: `perf_model.HardwareSpec` override for the cost models.
      distinct_slots: optional observed estimate of distinct slots touched
        per batch — the dynamic contention hint for `select_exchange`.
        Optional: when a `repro.tuning.SpecController` is running, repeated
        `execute_until` call sites get this estimate from the contention
        estimator (EWMA over combine-pass collision counts) automatically;
        pass it explicitly only to override the measured estimate.
      reverse_ranks: sharded tier only — serialize devices in *descending*
        rank order (the arrival order reversed at every exchange level).
        Combined with locally reversed batches this realizes a globally
        reversed op stream, the second pass of the SWP+revert BFS scheme.
      collect_stats: True additionally computes the batch's device-side
        :class:`~repro.atomics.stats.ContentionStats` inside the combine
        pass (occupancy, distinct/max/histogram, top-k hot slots; sharded
        tier adds per-exchange-level combining efficiency) — returned as
        ``result.stats``.  Results are bit-identical either way; with the
        default False the stats code does not run at all.

    Returns:
      :class:`AtomicResult`, bit-identical to the serialized oracle.
    """
    if not isinstance(table, AtomicTable):
        table = AtomicTable(table)
    if isinstance(ops, AtomicOp):
        table, fetched, success, stats = _execute_one(
            table, ops, need_fetched=need_fetched, backend=backend,
            strategy=strategy, spec=spec, distinct_slots=distinct_slots,
            reverse_ranks=reverse_ranks, collect_stats=collect_stats)
        return AtomicResult(table, fetched, success, stats)
    ops = tuple(ops)
    if not ops:
        raise ValueError("ops is empty")
    fetched_l, success_l, stats_l = [], [], []
    for op in ops:
        table, fetched, success, stats = _execute_one(
            table, op, need_fetched=need_fetched, backend=backend,
            strategy=strategy, spec=spec, distinct_slots=distinct_slots,
            reverse_ranks=reverse_ranks, collect_stats=collect_stats)
        fetched_l.append(fetched)
        success_l.append(success)
        stats_l.append(stats)
    return AtomicResult(table, tuple(fetched_l), tuple(success_l),
                        tuple(stats_l) if collect_stats else None)


def arrival_rank(keys: Array, num_keys: Optional[int] = None, *,
                 block: int = rmw_engine.DEFAULT_ONEHOT_BLOCK) -> Array:
    """Per-element arrival order among equal keys (0-based) — canonical.

    The FAA-fetch identity: ``rank[i]`` equals the fetched value of
    ``FAA(counter[key[i]], 1)`` executed in element order — the primitive
    MoE dispatch uses to assign each token its slot within its expert's
    capacity buffer.

    With ``num_keys`` (the static key-space size) the rank is computed
    **sort-free**: a dense one-hot cumsum for small key spaces, the blocked
    one-hot engine backend beyond.  Without it, falls back to the stable
    argsort + segmented-scan path (the only remaining use of that
    implementation — pass ``num_keys`` on hot paths).

    The one spelling (the two legacy per-tier functions this replaced —
    argsort in ``core.rmw``, sort-free in ``core.rmw_engine`` — are gone;
    their implementations live on as the private functions dispatched here).
    """
    if num_keys is None:
        return rmw_mod._arrival_rank_argsort(keys)
    return rmw_engine._arrival_rank_sortfree(keys, num_keys, block=block)
