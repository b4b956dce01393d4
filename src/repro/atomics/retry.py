"""`execute_until`: bounded-retry combinator for CAS loops, all tiers.

The paper's contention result (Fig. 8) and Lightweight Contention Management
(arxiv 1305.5800) agree on the fix for CAS storms: **failure feedback must
drive an explicit policy**, not blind retry.  A failed CAS already *fetched*
the winning value — that pre-image is exactly the next attempt's
``expected``, so a retry round never needs a separate read.  This module is
that loop as a combinator:

* each round executes one batched `atomics.execute` (local engine tier,
  or the sharded exchange tier when the table is mesh-sharded — the
  combinator launches its own ``shard_map``, scattering the round's ops
  over the devices in batch order);
* only the **failed** ops are re-batched, their fetched pre-images becoming
  the next round's per-op ``expected`` and their payloads recomputed by the
  caller's ``make_ops`` (the ``F`` in the lock-free ``CAS(x, v, F(v))``);
* a pluggable :class:`RetryPolicy` shapes the retry stream per
  arxiv 1305.5800 — retry everything at once (``immediate``), shrink the
  per-round batch so fewer ops collide (``shrink``), or space rounds with
  exponentially growing idle time (``exponential``);
* the result carries **per-op round counts** — the contention histogram a
  self-tuning policy needs is a free by-product of the loop.

Convergence: a fully-contended batch (every op targeting one slot) resolves
exactly one op per round — the serialized-equivalence contract means each
round's first arriving pending op sees its expected value and wins — so
``n`` ops need ``<= n`` rounds on every tier.  Uncontended batches resolve
in one.

Arrival-order caveat: *within* a round, ops execute in batch order (on a
mesh: the combinator scatters the round's batch contiguously over device
ranks, so device-rank concatenation re-creates batch order and local and
sharded tiers produce identical round histories).  *Across* rounds there is
no global order — a CAS loop is by construction order-free (each op commits
against whatever value it last observed), which is why `execute_until` may
be used where a single `execute` batch's serialized order matters not.
"""

from __future__ import annotations

import math
import time
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry
from repro.telemetry import core as _tcore
from repro.atomics import contracts as _contracts
from repro.atomics import stats as _cstats
from repro.atomics.layout import INT32_SLOTS
from repro.atomics.ops import OP_KINDS, AtomicOp, Cas
from repro.atomics.table import AtomicTable

Array = jax.Array


# ---------------------------------------------------------------------------
# Retry policies (arxiv 1305.5800: contention management as explicit policy)
# ---------------------------------------------------------------------------

class RetryPolicy:
    """How failures are re-offered: batch sizing + inter-round spacing.

    ``batch_size(n_pending, rnd)`` says how many of the pending ops round
    ``rnd`` may issue (the rest wait — fewer concurrent ops, less wasted
    work under contention); ``delay_s(rnd)`` is idle time *before* round
    ``rnd`` (0 for the first round).  Subclass to tune; the three classic
    shapes below are registered in :data:`POLICIES`.
    """

    name = "custom"

    def batch_size(self, n_pending: int, rnd: int) -> int:
        return n_pending

    def delay_s(self, rnd: int) -> float:
        return 0.0

    def __repr__(self):
        return f"{type(self).__name__}()"


class ImmediateRetry(RetryPolicy):
    """Re-offer every failed op next round, no spacing — optimal when the
    contention is *self-inflicted* (one batch against one table): each
    round's serialization resolves one winner per slot regardless."""

    name = "immediate"


class ShrinkBatch(RetryPolicy):
    """Halve (by default) the retry batch each consecutive failing round:
    the pending set still drains one winner per contended slot per round,
    but the losers that were going to fail anyway never hit the exchange —
    less wasted traffic, same round count."""

    name = "shrink"

    def __init__(self, factor: float = 0.5, min_batch: int = 1):
        if not 0.0 < factor <= 1.0:
            raise ValueError(f"factor must be in (0, 1], got {factor}")
        self.factor = factor
        self.min_batch = max(1, int(min_batch))

    def batch_size(self, n_pending: int, rnd: int) -> int:
        if rnd == 0:
            return n_pending
        return max(self.min_batch, math.ceil(n_pending * self.factor))


class ExponentialBackoff(RetryPolicy):
    """Full retry batches spaced by exponentially growing idle time —
    the classic shape when the contention is *external* (other writers
    between rounds), pointless when it is self-inflicted."""

    name = "exponential"

    def __init__(self, base_s: float = 1e-4, factor: float = 2.0,
                 max_s: float = 0.1):
        self.base_s = float(base_s)
        self.factor = float(factor)
        self.max_s = float(max_s)

    def delay_s(self, rnd: int) -> float:
        if rnd <= 0:
            return 0.0
        return min(self.max_s, self.base_s * self.factor ** (rnd - 1))


POLICIES: Dict[str, Callable[[], RetryPolicy]] = {
    "immediate": ImmediateRetry,
    "shrink": ShrinkBatch,
    "exponential": ExponentialBackoff,
}


def _resolve_policy(policy: Union[str, RetryPolicy]) -> RetryPolicy:
    if isinstance(policy, RetryPolicy):
        return policy
    try:
        return POLICIES[policy]()
    except KeyError:
        raise ValueError(f"unknown retry policy {policy!r}; have "
                         f"{tuple(POLICIES)} or a RetryPolicy instance")


# ---------------------------------------------------------------------------
# Result
# ---------------------------------------------------------------------------

class RetryResult(NamedTuple):
    """Outcome of :func:`execute_until` (host arrays, original batch order).

    ``fetched[i]`` is op i's *last observed pre-image* — for a resolved CAS,
    the value its winning attempt replaced; ``success[i]`` whether it
    resolved within the round budget; ``rounds[i]`` how many attempts it
    took (the per-op contention observable; 1 = first try); ``pending``
    the original positions still unresolved (empty on full convergence).

    ``stats`` is the round-0 device-side
    :class:`~repro.atomics.stats.ContentionStats` when the loop collected
    one (``collect_stats=True``, or None-auto with a tuning controller
    running), else None.  Round 0 is the full batch — the round whose
    contention spectrum characterizes the workload; later rounds only
    re-issue the losers.
    """

    table: AtomicTable
    fetched: np.ndarray
    success: np.ndarray
    rounds: np.ndarray
    n_rounds: int
    pending: np.ndarray
    stats: Any = None


# ---------------------------------------------------------------------------
# Sharded round execution: the combinator's own shard_map per round
# ---------------------------------------------------------------------------

_SHARDED_ROUND_CACHE: Dict[tuple, Any] = {}


def _norm_tuple(axes) -> Tuple[str, ...]:
    if axes is None:
        return ()
    return (axes,) if isinstance(axes, str) else tuple(axes)


def _sharded_round_fn(mesh, axis: Tuple[str, ...], rep: Tuple[str, ...],
                      kind: str, backend: str, strategy: str, spec,
                      distinct_slots, collect_stats: bool = False):
    """Build (and cache) the jitted shard_map executing ONE retry round on
    a mesh-sharded table: ops scattered contiguously over device ranks, so
    the device-rank arrival order re-creates the round's batch order."""
    from repro.core import rmw_engine
    # the spec epoch invalidates cached rounds when the tuning controller
    # swaps the live spec: the body bakes its strategy selection at trace
    # time, so a stale entry would keep dispatching the old choice
    key = (mesh, axis, rep, kind, backend, strategy, id(spec),
           distinct_slots, collect_stats, rmw_engine._SPEC_EPOCH)
    fn = _SHARDED_ROUND_CACHE.get(key)
    if fn is not None:
        return fn
    from jax.sharding import PartitionSpec as P

    from repro.atomics.execute import execute
    from repro.atomics.stats import ContentionStats
    from repro.sharding import shard_map_compat

    tab_spec, op_spec = P(axis), P(rep + axis)

    def body(t, i, v, e):
        tbl = AtomicTable(t, axis=axis if len(axis) > 1 else axis[0],
                          replica_axes=rep)
        if kind == "cas":
            op = Cas(i, v, expected=e)
        else:
            op = OP_KINDS[kind](i, v)
        res = execute(tbl, op, need_fetched=True, backend=backend,
                      strategy=strategy, spec=spec,
                      distinct_slots=distinct_slots,
                      collect_stats=collect_stats)
        if collect_stats:
            return res.table.data, res.fetched, res.success, res.stats
        return res.table.data, res.fetched, res.success

    out_specs = (tab_spec, op_spec, op_spec)
    if collect_stats:
        # stats leaves are already psum'd over every mesh axis inside the
        # exchange — replicated outputs, P() per ContentionStats field
        out_specs = out_specs + (
            ContentionStats(*([P()] * len(ContentionStats._fields))),)
    fn = jax.jit(shard_map_compat(body, mesh,
                                  (tab_spec, op_spec, op_spec, op_spec),
                                  out_specs))
    _SHARDED_ROUND_CACHE[key] = fn
    return fn


def _exec_round_sharded(table: AtomicTable, kind: str, idx: np.ndarray,
                        vals: np.ndarray, exp: Optional[np.ndarray], *,
                        backend: str, strategy: str, spec, distinct_slots,
                        collect_stats: bool = False):
    from repro import sharding as shardlib
    mesh = getattr(getattr(table.data, "sharding", None), "mesh", None)
    if mesh is None:
        mesh = shardlib.active_mesh()
    if mesh is None:
        raise ValueError(
            "execute_until on a sharded AtomicTable needs the mesh: place "
            "the table data with a NamedSharding (make_table under "
            "use_mesh) or call under sharding.use_mesh — the combinator "
            "launches its own shard_map per round, so unlike execute() it "
            "must be called OUTSIDE shard_map")
    axis, rep = _norm_tuple(table.axis), _norm_tuple(table.replica_axes)
    n_dev = math.prod(mesh.shape[a] for a in rep + axis)
    m = int(table.data.shape[0])
    k = len(idx)
    # pad per-device count to a power of two: bounded recompile count as
    # the pending set drains, padding ops target slot m (the OOR-drop
    # convention: no table effect, fetched 0, success False — sliced off)
    per = 1 << max(0, (max(1, -(-k // n_dev)) - 1)).bit_length()
    total = per * n_dev
    tbl_dtype = np.asarray(jnp.zeros((), table.data.dtype)).dtype
    idx_p = np.full(total, m, np.int32)
    idx_p[:k] = idx
    vals_p = np.zeros(total, tbl_dtype)
    vals_p[:k] = vals
    exp_p = np.zeros(total, tbl_dtype)
    if exp is not None:
        exp_p[:k] = exp
    fn = _sharded_round_fn(mesh, axis, rep, kind, backend, strategy, spec,
                           distinct_slots, collect_stats)
    from jax.sharding import NamedSharding, PartitionSpec as P
    op_sh = NamedSharding(mesh, P(rep + axis))
    args = [jax.device_put(jnp.asarray(a), op_sh)
            for a in (idx_p, vals_p, exp_p)]
    info = None
    if telemetry.enabled():
        # the prediction half of the round event: per-op CAS routes to the
        # owner-oracle pass (unpriced); everything else is a combinable
        # exchange the selector can price per strategy
        info = {"tier": "sharded", "n_exec": per, "m": m,
                "n_shards": n_dev // max(1, _rep_size(mesh, rep)),
                "strategy": "perop_oracle", "predicted_s": None}
        if kind != "cas":
            try:
                from repro.core import rmw_sharded as rs
                sizes = [int(mesh.shape[a]) for a in axis]
                sel = rs.select_exchange_with_cost(
                    kind, per, m, rs._mesh_axes(axis, sizes, None),
                    spec=spec, need_fetched=True,
                    distinct_slots=distinct_slots) if strategy == "auto" \
                    else None
                if sel is not None:
                    info.update(strategy=sel.choice,
                                predicted_s=sel.predicted_s)
                else:
                    info.update(strategy=strategy)
            except Exception:  # noqa: BLE001 — never break the round
                pass
    stats = None
    with telemetry.span("atomics.retry.exchange", n=k, n_exec=per):
        if collect_stats:
            tab, fetched, success, stats = fn(table.data, *args)
        else:
            tab, fetched, success = fn(table.data, *args)
    return (table.with_data(tab), np.asarray(fetched)[:k],
            np.asarray(success)[:k].astype(bool), info, stats)


def _rep_size(mesh, rep: Tuple[str, ...]) -> int:
    return math.prod(int(mesh.shape[a]) for a in rep) if rep else 1


def _exec_round(table: AtomicTable, kind: str, idx: np.ndarray,
                vals: np.ndarray, exp: Optional[np.ndarray], *,
                backend: str, strategy: str, spec, distinct_slots,
                collect_stats: bool = False):
    if table.is_sharded:
        return _exec_round_sharded(table, kind, idx, vals, exp,
                                   backend=backend, strategy=strategy,
                                   spec=spec, distinct_slots=distinct_slots,
                                   collect_stats=collect_stats)
    from repro.atomics.execute import execute
    if kind == "cas":
        op = Cas(jnp.asarray(idx), jnp.asarray(vals),
                 expected=jnp.asarray(exp))
    else:
        op = OP_KINDS[kind](jnp.asarray(idx), jnp.asarray(vals))
    info = None
    if telemetry.enabled():
        from repro.core import rmw_engine
        m = int(table.data.shape[0])
        info = {"tier": "local", "n_exec": len(idx), "m": m,
                "strategy": None, "predicted_s": None}
        try:
            sel = rmw_engine.select_backend_with_cost(
                kind, len(idx), m, spec,
                uniform_expected=kind != "cas", dtype=table.dtype) \
                if backend == "auto" else None
            if sel is not None:
                info.update(backend=sel.choice, predicted_s=sel.predicted_s)
            else:
                info.update(backend=backend)
        except Exception:  # noqa: BLE001 — never break the round
            pass
    res = execute(table, op, need_fetched=True, backend=backend, spec=spec,
                  collect_stats=collect_stats)
    return (res.table, np.asarray(res.fetched),
            np.asarray(res.success).astype(bool), info, res.stats)


# ---------------------------------------------------------------------------
# The combinator
# ---------------------------------------------------------------------------

def _host_distinct(x: np.ndarray) -> int:
    """Round-0 host-side distinct-slot count — the pre-observatory
    estimator observation, kept as the fallback when device-side stats are
    off (and monkeypatchable in tests to prove the hot path skips it)."""
    return int(np.unique(x).size)


def _active_estimator():
    """The running `repro.tuning` controller's contention estimator, or
    None.  sys.modules probing (not an import) keeps `repro.atomics` free
    of the tuning package unless a controller was actually started."""
    import sys
    mod = sys.modules.get("repro.tuning.controller")
    if mod is None:
        return None
    return mod.active_estimator()


def execute_until(table: Union[AtomicTable, Array],
                  make_ops: Callable, *,
                  max_rounds: int = 16,
                  policy: Union[str, RetryPolicy] = "immediate",
                  backend: str = "auto", strategy: str = "auto",
                  spec=None, distinct_slots: Optional[int] = None,
                  collect_stats: Optional[bool] = None,
                  sleep_fn: Callable[[float], None] = time.sleep
                  ) -> RetryResult:
    """Drive a batch of CAS loops to convergence in ``<= max_rounds`` rounds.

    ``make_ops`` is called twice per shape of the loop:

    * ``make_ops(None, None)`` (round 0) must return the initial
      :class:`~repro.atomics.ops.AtomicOp` batch — typically a ``Cas``
      (scalar or per-op ``expected``); any other op kind trivially resolves
      in one round.
    * ``make_ops(slots, observed)`` (later rounds) receives the still-
      pending ops' table slots and their latest fetched pre-images and
      returns the new *values* array for exactly those ops (the ``F`` in
      the lock-free ``CAS(x, v, F(v))``), or a full ``AtomicOp`` over them
      to also override ``expected``, or ``None`` to give up early.  The
      combinator supplies ``expected = observed`` — the CAS-failure
      feedback loop of arxiv 1305.5800.

    The table may be local or mesh-sharded; for a sharded table the
    combinator launches its own ``shard_map`` per round (call it *outside*
    ``shard_map``), scattering each round's pending ops contiguously over
    device ranks so both tiers produce identical round histories.

    Returns a :class:`RetryResult`; ``success`` is all-True iff every op
    resolved within the budget, and ``rounds`` is the per-op contention
    observable (attempts until success).

    ``distinct_slots`` (the exchange selector's contention hint) is
    estimator-backed: when a `repro.tuning.SpecController` is running and
    the caller passes None, the hint comes from the contention estimator's
    EWMA over this call site's observed collision counts (round-0 distinct
    slots + CAS round-histogram winners).  Passing an explicit value
    overrides the estimator; without a controller, None means no hint —
    exactly the pre-tuning behavior.

    ``collect_stats`` controls the round-0 device-side contention pass
    (:class:`~repro.atomics.stats.ContentionStats`, returned in
    ``result.stats``): True forces it, False forces it off, and the
    default None enables it exactly when an estimator is active — the
    estimator then reads ``distinct_slots`` straight from the combine
    pass instead of the host ``np.unique`` fallback, which is skipped
    entirely.  Results are bit-identical in every mode.
    """
    pol = _resolve_policy(policy)
    if max_rounds < 1:
        raise ValueError(f"max_rounds must be >= 1, got {max_rounds}")
    if _contracts._observer is not None:
        # contract annotation for the analyzer: this loop IS round-bounded
        # by construction (rule A003's recommended spelling)
        _contracts.notify("execute_until", table=table,
                          max_rounds=max_rounds, policy=pol.name)
    if not isinstance(table, AtomicTable):
        table = AtomicTable(table)
    op0 = make_ops(None, None)
    if not isinstance(op0, AtomicOp):
        raise TypeError(
            f"make_ops(None, None) must return an atomics op batch "
            f"(got {type(op0).__name__}) — e.g. "
            f"atomics.Cas(indices, values, expected=...)")
    kind = op0.kind
    n = int(op0.indices.shape[0])
    if int(table.data.shape[0]) > INT32_SLOTS:
        raise ValueError(
            f"execute_until tracks pending slots as int32, up to "
            f"{INT32_SLOTS}; this table has {int(table.data.shape[0])}")
    # contention estimator (repro.tuning): when a controller is running
    # and the caller passed no hint, serve the site's EWMA'd observed
    # distinct-slot count as the exchange selector's contention hint —
    # "estimator-backed, hint optional".  Selection-only, like the hint
    # itself: it can never change results.
    est = _active_estimator()
    est_key = None
    if est is not None:
        from repro.tuning.estimator import site_key
        est_key = site_key(kind,
                           "sharded" if table.is_sharded else "local",
                           int(table.data.shape[0]), n)
        if distinct_slots is None and table.is_sharded:
            distinct_slots = est.hint(est_key)
    # device-side stats default: on exactly when an estimator consumes
    # them (the ROADMAP follow-on: feed the EWMA from on-device counts)
    use_device = collect_stats if collect_stats is not None \
        else est is not None
    stats0 = None
    tbl_dtype = np.asarray(jnp.zeros((), table.data.dtype)).dtype
    slots = np.asarray(op0.indices, np.int32).copy()
    values = np.asarray(op0.values, tbl_dtype).copy()
    is_cas = kind == "cas"
    if is_cas:
        expected = np.broadcast_to(
            np.asarray(op0.expected, tbl_dtype), (n,)).copy()
    else:
        expected = None
    observed = (expected.copy() if is_cas
                else np.zeros(n, tbl_dtype))   # latest pre-image per op
    success = np.zeros(n, bool)
    rounds = np.zeros(n, np.int64)
    pending = np.arange(n)

    n_rounds = 0
    while len(pending) and n_rounds < max_rounds:
        rnd = n_rounds
        if rnd > 0:
            d = pol.delay_s(rnd)
            if d > 0:
                sleep_fn(d)
            made = make_ops(slots[pending], observed[pending])
            if made is None:
                break
            if isinstance(made, AtomicOp):
                if made.kind != kind or \
                        int(made.indices.shape[0]) != len(pending):
                    raise ValueError(
                        f"make_ops must re-batch exactly the pending ops: "
                        f"wanted {len(pending)} {kind!r} ops, got "
                        f"{int(made.indices.shape[0])} {made.kind!r}")
                slots[pending] = np.asarray(made.indices, np.int32)
                values[pending] = np.asarray(made.values, tbl_dtype)
                if is_cas:
                    expected[pending] = np.broadcast_to(
                        np.asarray(made.expected, tbl_dtype),
                        (len(pending),))
            else:
                vals_new = np.asarray(made, tbl_dtype)
                if vals_new.shape != (len(pending),):
                    raise ValueError(
                        f"make_ops returned values of shape "
                        f"{vals_new.shape}; want ({len(pending)},) — one "
                        f"value per pending op")
                values[pending] = vals_new
                if is_cas:
                    # the feedback loop: pre-image becomes next expected
                    expected[pending] = observed[pending]
        k = max(1, min(pol.batch_size(len(pending), rnd), len(pending)))
        issue, defer = pending[:k], pending[k:]
        collect_now = use_device and rnd == 0
        if rnd == 0 and not use_device and (est is not None
                                            or telemetry.enabled()):
            # host fallback for the combine pass's collision count: the
            # slots are host numpy already, so the round-0 distinct-slot
            # count is one np.unique away — skipped entirely when the
            # device pass supersedes it or nothing consumes it
            distinct_obs = _host_distinct(slots[issue])
            if est is not None:
                est.update(est_key, distinct_obs)
        else:
            distinct_obs = None
        t0 = time.perf_counter()
        table, fetched, ok, info, st = _exec_round(
            table, kind, slots[issue], values[issue],
            expected[issue] if is_cas else None,
            backend=backend, strategy=strategy, spec=spec,
            distinct_slots=distinct_slots, collect_stats=collect_now)
        if st is not None:
            stats0 = st
            # the round's fetched/success reads just blocked, so the stats
            # leaves are materialized — reading distinct here is one D2H
            # scalar copy, not a sync
            distinct_obs = int(np.asarray(st.distinct_slots))
            if est is not None:
                est.update(est_key, distinct_obs, source="device")
        if info is not None:
            if distinct_obs is not None:
                info["distinct_observed"] = distinct_obs
            # one event per retry round: the pending-count trajectory is
            # the contention signal the ROADMAP's adaptive estimator needs,
            # and (predicted_s, measured_s) feed the exchange-tier drift
            # tracker (the round's fetched/success reads block, so the
            # measured wall covers the full round dispatch+execute)
            telemetry.record(
                "atomics.retry.round", op=kind, policy=pol.name, round=rnd,
                pending=len(pending), issued=int(k),
                resolved=int(ok.sum()),
                measured_s=time.perf_counter() - t0, **info)
        observed[issue] = fetched
        rounds[issue] += 1
        success[issue] = ok
        # freshly failed ops lead the next round: their pre-images are
        # current, so a round issuing any of them always makes progress;
        # deferred ops (stale pre-images under a shrinking policy) trail
        pending = np.concatenate([issue[~ok], defer])
        n_rounds += 1

    if est is not None and is_cas and n_rounds >= 1:
        # the round histogram's second observation of the same quantity:
        # ops resolved on their FIRST attempt = one winner per contended
        # slot + every uncontended op = distinct slots among the issued
        # batch (CAS only — weaker ops resolve in one round regardless)
        est.update(est_key, int(((rounds == 1) & success).sum()))
    if telemetry.enabled():
        # rounds[i] = attempts op i took; bincount over it is the per-call
        # contention histogram (index = attempt count, 0 = never issued)
        hist = np.bincount(rounds.astype(np.int64),
                           minlength=n_rounds + 1).tolist()
        telemetry.record("atomics.retry.done", op=kind, policy=pol.name,
                         n=n, n_rounds=n_rounds,
                         tier="sharded" if table.is_sharded else "local",
                         resolved=int(success.sum()),
                         unresolved=int(len(pending)),
                         attempts=int(rounds.sum()), round_histogram=hist)
        if stats0 is not None and (table.is_sharded or not _tcore._sync):
            # the loop's own sync boundary; the local-tier + sync-mode
            # combination is the one case execute()'s eager sync branch
            # already emitted, so it is excluded to keep one event per
            # collected batch
            telemetry.record_event(_cstats.stats_to_fields(
                stats0, tier="sharded" if table.is_sharded else "local",
                op=kind, n=n, m=int(table.data.shape[0]), round=0))
    return RetryResult(table=table, fetched=observed, success=success,
                       rounds=rounds, n_rounds=n_rounds,
                       pending=np.sort(pending), stats=stats0)
