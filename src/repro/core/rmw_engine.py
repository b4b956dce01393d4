"""Sort-free combining-RMW engine: backend registry + model-driven dispatch.

The paper's fix for serialized atomics is software combining (§6.2.3); the
repo's original realization (`core.rmw.rmw_combining`) pays a stable
``argsort`` + segmented scan per batch — O(n log n) sort-dominated work that
TPUs execute poorly.  This module turns RMW execution into a pluggable
**backend engine**:

``serialized``
    The order-faithful ``lax.scan`` oracle (`core.rmw.rmw_serialized`) — the
    paper's measured hardware, and the only backend for general per-op
    expected CAS (the un-combinable "wasted work" case).
``sort``
    The existing argsort + segmented-scan combiner (`core.rmw.rmw_combining`)
    — the general-purpose path, still best for huge tables with tiny batches.
``onehot``
    NEW, sort-free: processes the batch in blocks, carrying the table between
    blocks.  Within a block, *fetched values* come from a strict-lower-
    triangular same-key contraction (an MXU-shaped (B,B) @ (B,) matmul) plus
    a gather of the carried table; table updates are plain bincount-style
    scatters.  O(n·B) contraction work instead of O(n log n) sort — no
    argsort anywhere.
``pallas``
    The Mosaic one-hot-matmul kernel (`kernels.rmw.ops.rmw_apply_fetched`);
    table tiles stay VMEM-resident across the index-block grid axis.  fp32
    tables only; the fetched path (and CAS) up to
    ``PALLAS_FETCHED_MAX_SLOTS`` slots.

Every backend produces results bit-identical to ``rmw_serialized`` for every
op it supports (integer dtypes; float FAA is exact up to reassociation, the
same caveat the sort backend always had).  CAS is supported in combinable
form for a *uniform* expected value; per-op expected arrays fall back to the
oracle.

Selection (`select_backend`) is the paper's L(A, S) model used as an actual
runtime decision procedure: each backend exposes a predicted cost built from
:class:`repro.core.perf_model.HardwareSpec` constants (op, batch size, table
size -> seconds), and the cheapest *correct* backend wins.  ``execute_backend``
is the canonical entry, reached through the unified front-end
`repro.atomics.execute` (the PR-3 ``rmw_execute`` / ``arrival_rank`` shims
served their one-release window and are deleted).  The constants were tuned
from the committed ``benchmarks/results/rmw_backends.json`` sweep (see
README "RMW engine").
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
from functools import partial
from typing import Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro import telemetry
from repro.core import perf_model
from repro.core.placement import PlacementState, Tier
from repro.core.rmw import (OPS, RmwResult, _identity, rmw_combining,
                            rmw_serialized)

Array = jax.Array

#: default batch-block edge for the blocked one-hot backend (B x B same-key
#: contraction per block; 128 balances the O(B^2) intra-block traffic against
#: the per-block table-carry cost — see benchmarks/results/rmw_backends.json)
DEFAULT_ONEHOT_BLOCK = 128


def _is_uniform_expected(expected) -> bool:
    """True when CAS `expected` is one shared value (combinable form)."""
    if expected is None:
        return False
    return jnp.ndim(expected) == 0


# ---------------------------------------------------------------------------
# The sort-free one-hot backend
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("op", "block", "need_fetched"))
def rmw_onehot(table: Array, indices: Array, values: Array, op: str,
               expected: Optional[Array] = None, *,
               block: int = DEFAULT_ONEHOT_BLOCK,
               need_fetched: bool = True) -> RmwResult:
    """Serialized-equivalent RMW batch with **no argsort**.

    The batch is cut into blocks of ``block`` ops.  A ``lax.scan`` carries the
    table (plus one scratch row for dropped/padding ops) across blocks; within
    a block the exclusive per-slot prefix each op observes is

        prefix[i] = combine_{j<i, idx[j]==idx[i]} values[j]

    computed from the strict-lower-triangular same-key mask — for FAA that is
    exactly the lower-triangular-masked one-hot matmul ``(L ∘ same) @ v``.
    ``fetched[i] = combine(table_carry[idx[i]], prefix[i])``.

    ``need_fetched=False`` skips the prefix machinery entirely and computes
    the final table in one bincount-style scatter pass (O(n + m), no blocks,
    no carry) — the right mode for table-only callers (gradient scatter,
    histograms, BFS CAS parents).  The returned ``fetched``/``success`` are
    then all-zeros placeholders; only ``.table`` is meaningful.

    Indices outside [0, table size) are routed to the scratch row (dropped),
    matching the Pallas kernel's masking convention; their fetched/success
    outputs are meaningless.
    """
    if op not in OPS:
        raise ValueError(f"unknown op {op!r}")
    if op == "cas" and expected is None:
        raise ValueError("cas requires `expected`")
    if not need_fetched:
        return _tables_only(table, indices, values, op, expected)

    n = indices.shape[0]
    m = table.shape[0]
    b = int(min(block, max(8, n)))
    pad = (-n) % b
    nb = (n + pad) // b

    idx = indices.astype(jnp.int32)
    idx = jnp.where((idx < 0) | (idx > m), m, idx)       # m == scratch row
    idx = jnp.concatenate([idx, jnp.full((pad,), m, jnp.int32)])
    val = jnp.concatenate([values, jnp.zeros((pad,), values.dtype)])
    acc0 = jnp.concatenate([table, table[:1]])           # scratch row at m

    pos = jnp.arange(b, dtype=jnp.int32)
    tri = pos[:, None] > pos[None, :]                    # strict lower (B,B)
    exp = None if expected is None else jnp.asarray(expected, table.dtype)

    def step(acc, xs):
        ib, vb = xs                                       # (B,), (B,)
        same = (ib[:, None] == ib[None, :]) & tri         # j < i, same slot
        base = acc[ib]                                    # carried table value

        if op == "faa":
            prefix = same.astype(vb.dtype) @ vb           # tri-masked matmul
            fetched = base + prefix
            ok = jnp.ones((b,), bool)
            with jax.named_scope("rmw.scatter"):
                acc = acc.at[ib].add(vb)
        elif op in ("min", "max"):
            ident = _identity(op, vb.dtype)
            comb = jnp.minimum if op == "min" else jnp.maximum
            masked = jnp.where(same, vb[None, :], ident)
            prefix = (jnp.min(masked, axis=1) if op == "min"
                      else jnp.max(masked, axis=1))
            fetched = comb(base, prefix)
            ok = jnp.ones((b,), bool)
            with jax.named_scope("rmw.scatter"):
                acc = acc.at[ib].min(vb) if op == "min" \
                    else acc.at[ib].max(vb)
        elif op == "swp":
            mpos = jnp.where(same, pos[None, :], -1).max(axis=1)
            prev = vb[jnp.clip(mpos, 0)]
            fetched = jnp.where(mpos >= 0, prev, base)
            ok = jnp.ones((b,), bool)
            # last collider per slot wins; earlier ones go to the scratch row
            later_same = (ib[:, None] == ib[None, :]) \
                & (pos[:, None] < pos[None, :])
            is_last = ~later_same.any(axis=1)
            with jax.named_scope("rmw.scatter"):
                acc = acc.at[jnp.where(is_last, ib, m)].set(vb)
        else:  # cas, uniform expected
            # Serialized CAS chains compose associatively: the slot's value
            # after a collider group is `first value != expected` (writes of
            # the expected value keep the chain alive).  See core.rmw.
            ne = vb != exp
            fpos = jnp.where(same & ne[None, :], pos[None, :], b).min(axis=1)
            x_excl = jnp.where(fpos < b, vb[jnp.clip(fpos, 0, b - 1)], exp)
            v_before = jnp.where(base == exp, x_excl, base)
            fetched = v_before
            ok = v_before == exp
            # block winner = first op with value != expected at a live slot
            is_first_ne = ne & (fpos == b)
            write = is_first_ne & (base == exp)
            with jax.named_scope("rmw.scatter"):
                acc = acc.at[jnp.where(write, ib, m)].set(vb)
        return acc, (fetched, ok)

    acc, (fetched, ok) = jax.lax.scan(
        step, acc0, (idx.reshape(nb, b), val.reshape(nb, b)))
    return RmwResult(acc[:m], fetched.reshape(-1)[:n], ok.reshape(-1)[:n])


def _tables_only(table: Array, indices: Array, values: Array, op: str,
                 expected: Optional[Array]) -> RmwResult:
    """Final table in one scatter pass (the sort-free 'bincount' core).

    Out-of-range-high indices drop via XLA's native scatter semantics (the
    same convention the sort backend's scatters use); negative indices are
    remapped past the table so they drop too instead of wrapping
    NumPy-style — matching the fetched path on identical inputs.
    """
    with jax.named_scope("rmw.scatter"):
        n = indices.shape[0]
        m = table.shape[0]
        idx = indices.astype(jnp.int32)
        idx = jnp.where(idx < 0, jnp.int32(m), idx)
        pos = jnp.arange(n, dtype=jnp.int32)
        if op == "faa":
            tab = table.at[idx].add(values)
        elif op in ("min", "max"):
            tab = (table.at[idx].min(values) if op == "min"
                   else table.at[idx].max(values))
        elif op == "swp":
            last = jnp.full((m,), -1, jnp.int32).at[idx].max(pos)
            tab = jnp.where(last >= 0, values[jnp.clip(last, 0)], table)
        else:  # cas, uniform expected: slot = first value != expected if live
            e = jnp.asarray(expected, table.dtype)
            first = jnp.full((m,), n, jnp.int32).at[idx].min(
                jnp.where(values != e, pos, n))
            tab = jnp.where((table == e) & (first < n),
                            values[jnp.clip(first, 0, n - 1)], table)
        return RmwResult(tab, jnp.zeros((n,), values.dtype),
                         jnp.zeros((n,), bool))


def slot_occupancy(indices: Array, m: int) -> Array:
    """(m,) int32 per-slot writer counts for a batch of slot indices.

    This *is* the onehot backend's bincount pass (`_tables_only` FAA with
    unit values) exposed for the contention observatory (PR 10) instead of
    recomputed: out-of-range-high indices drop, negatives are remapped past
    the table so they drop too — exactly the occupancy the combine passes
    act on.  Pure jnp; traces inside jit/shard_map.
    """
    ones = jnp.ones(indices.shape, jnp.int32)
    return _tables_only(jnp.zeros((m,), jnp.int32), indices, ones,
                        "faa", None).table


@partial(jax.jit, static_argnames=("num_keys", "block"))
def _arrival_rank_sortfree(keys: Array, num_keys: int, *,
                           block: int = DEFAULT_ONEHOT_BLOCK) -> Array:
    """Sort-free per-element arrival order among equal keys (0-based).

    The FAA-fetch identity: rank[i] = fetched value of FAA(counter[key], 1)
    executed in element order.  For small key spaces a dense one-hot cumsum
    (one associative scan, MXU/VPU friendly) wins; for large ones the blocked
    one-hot backend computes the same thing without materializing (n, K).
    Public spelling: `repro.atomics.arrival_rank` (this module's old
    `arrival_rank` shim around this function is deleted).
    """
    n = keys.shape[0]
    k = jnp.asarray(keys, jnp.int32)
    if n * num_keys <= (1 << 22):
        onehot = (k[:, None] == jnp.arange(num_keys, dtype=jnp.int32)[None, :])
        incl = jnp.cumsum(onehot.astype(jnp.int32), axis=0)
        return jnp.take_along_axis(incl, k[:, None], axis=1)[:, 0] - 1
    res = rmw_onehot(jnp.zeros((num_keys,), jnp.int32), k,
                     jnp.ones((n,), jnp.int32), "faa", block=block)
    return res.fetched


# ---------------------------------------------------------------------------
# Predicted-cost models (the paper's L(A,S) as a decision procedure)
# ---------------------------------------------------------------------------

def _op_for_model(op: str) -> str:
    # perf_model's RMW_OPS has no min/max; they execute like FAA (one
    # combine ALU op on the fetched line).
    return op if op in perf_model.RMW_OPS else "faa"


def _table_tier(nbytes: int) -> Tier:
    """Working tier of the table: on-chip while it fits, HBM/DRAM beyond."""
    return Tier.VMEM if nbytes <= (1 << 21) else Tier.HBM_LOCAL


def _table_state(m: int, itemsize: int = 4) -> PlacementState:
    return PlacementState(tier=_table_tier(m * itemsize))


def cost_serialized(spec: perf_model.HardwareSpec, op: str, n: int, m: int,
                    need_fetched: bool = True) -> float:
    """n dependent atomics, each paying the paper's full L(A, S).

    The software oracle additionally pays one scan step per op (hardware
    atomics would not), so the same `loop_step_s` constant applies per op.
    """
    per_op = perf_model.latency(spec, _op_for_model(op), _table_state(m))
    return n * (per_op + (spec.loop_step_s or 1e-6))


def cost_sort(spec: perf_model.HardwareSpec, op: str, n: int, m: int,
              need_fetched: bool = True) -> float:
    """argsort (log2 n passes) + log-depth segmented scan + gather/scatter."""
    sort_pass = spec.sort_elem_pass_s or 8.0 / max(spec.combine_ops_per_s, 1.0)
    gather = spec.gather_elem_s or sort_pass / 2
    passes = max(1.0, math.log2(max(n, 2)))
    scan = max(1.0, math.log2(max(n, 2))) / max(spec.combine_ops_per_s, 1.0)
    return n * passes * sort_pass + n * scan + 4 * n * gather


def cost_onehot(spec: perf_model.HardwareSpec, op: str, n: int, m: int,
                need_fetched: bool = True,
                block: int = DEFAULT_ONEHOT_BLOCK) -> float:
    """Blocked: ceil(n/B) x (B^2 contraction + table carry); scatter-only
    (O(n + m) bincount) when fetched values aren't needed."""
    gather = spec.gather_elem_s or 2e-9
    if not need_fetched:
        return (n + m) * gather
    b = min(block, max(8, n))
    blocks = -(-n // b)
    step = spec.loop_step_s or 1e-6
    mac = 2.0 * b * b / max(spec.peak_flops, 1.0)
    # each scan step re-materializes the carried table (copy traffic), and
    # gathers degrade once the table spills the on-chip tier
    carry = 4.0 * m / max(spec.tier_bandwidth_Bps[_table_tier(4 * m)], 1.0)
    tier_pen = 1.0 if _table_tier(4 * m) is Tier.VMEM else 2.0
    return blocks * (mac + step + carry) + 3.0 * n * gather * tier_pen


def cost_pallas(spec: perf_model.HardwareSpec, op: str, n: int, m: int,
                need_fetched: bool = True) -> float:
    """One-hot contraction over every (table-tile, index-block) pair."""
    if jax.default_backend() != "tpu":
        # interpret mode: each grid step is Python-dispatched jnp — only ever
        # competitive in this container for validation, never for speed.
        return 1e-3 * max(1, (m // 512)) * max(1, (n // 1024)) + 1e-2
    return (2.0 * n * m / max(spec.peak_flops, 1.0)
            + (4.0 * (n + m)) / max(spec.hbm_Bps, 1.0))


# ---------------------------------------------------------------------------
# Backend registry
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class RmwBackend:
    """One way of executing an RMW batch, plus its predicted-cost model."""

    name: str
    ops: frozenset                      # ops with serialized-equivalent results
    run: Callable[..., RmwResult]       # (table, indices, values, op,
                                        #  expected, need_fetched=...)
    cost: Callable[..., float]          # (spec, op, n, m, need_fetched)
    general_cas: bool = False           # per-op expected arrays supported?
    float_table_only: bool = False      # e.g. the fp32 Pallas kernel
    #: largest table its fetched path (CAS always fetches) accepts, or None
    max_fetched_slots: Optional[int] = None

    def supports(self, op: str, *, uniform_expected: bool = True,
                 dtype=None, m: Optional[int] = None,
                 need_fetched: bool = True) -> bool:
        if op not in self.ops:
            return False
        if self.max_fetched_slots is not None and m is not None \
                and (need_fetched or op == "cas") \
                and m > self.max_fetched_slots:
            return False
        if op == "cas" and not uniform_expected and not self.general_cas:
            return False
        if self.float_table_only and dtype is not None \
                and not jnp.issubdtype(dtype, jnp.floating):
            return False
        return True


#: the Pallas fetched kernel compiles one program per 512-slot table tile
#: (`kernels.rmw.kernel.rmw_table_fetched`): 32 tiles take ~3 s of TPU
#: compile, and the count grows linearly, so larger tables are refused
PALLAS_FETCHED_MAX_SLOTS = 1 << 14


def _run_pallas(table, indices, values, op, expected=None,
                need_fetched=True):
    from repro.kernels.rmw import ops as kops   # deferred: keeps core import-light
    if not need_fetched and op != "cas":
        out = kops.rmw_apply(table, indices, values, op)
        return RmwResult(out, jnp.zeros(indices.shape, table.dtype),
                         jnp.zeros(indices.shape, bool))
    return kops.rmw_apply_fetched(table, indices, values, op,
                                  expected=expected)


BACKENDS: Dict[str, RmwBackend] = {}


def register_backend(backend: RmwBackend) -> None:
    BACKENDS[backend.name] = backend


register_backend(RmwBackend(
    name="serialized", ops=frozenset(OPS),
    run=lambda t, i, v, op, e=None, need_fetched=True:
        rmw_serialized(t, i, v, op, e),
    cost=cost_serialized, general_cas=True))
register_backend(RmwBackend(
    name="sort", ops=frozenset(OPS),
    run=lambda t, i, v, op, e=None, need_fetched=True:
        rmw_combining(t, i, v, op, e),
    cost=cost_sort))
register_backend(RmwBackend(
    name="onehot", ops=frozenset(OPS),
    run=lambda t, i, v, op, e=None, need_fetched=True:
        rmw_onehot(t, i, v, op, e, need_fetched=need_fetched),
    cost=cost_onehot))
register_backend(RmwBackend(
    name="pallas", ops=frozenset(("faa", "min", "max", "swp", "cas")),
    run=_run_pallas, cost=cost_pallas, float_table_only=True,
    max_fetched_slots=PALLAS_FETCHED_MAX_SLOTS))


def calibrated_spec_path() -> str:
    """Where `benchmarks/calibrate.py` persists the fitted CPU spec.

    Overridable via ``REPRO_CALIBRATED_SPEC`` (tests use this); the default
    is the committed benchmark-results location at the repo root.
    """
    import os
    env = os.environ.get("REPRO_CALIBRATED_SPEC")
    if env:
        return env
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    return os.path.join(root, "benchmarks", "results",
                        "calibrated_spec.json")


_SPEC_CACHE: Dict[str, perf_model.HardwareSpec] = {}

# Process-wide "live spec" override (`repro.tuning.SpecController` installs
# its tuned spec here).  All three selector tiers default their spec through
# `default_spec()`, so this single indirection swaps the active cost model
# everywhere at once.  The epoch counter is bumped on every swap; decision
# caches keyed on it (atomics.execute, atomics.retry) invalidate themselves
# the moment a new spec lands.  The spec only ever steers *selection* —
# every backend/strategy is bit-identical to the serialized oracle — so a
# live swap can never change results, only which implementation runs.
_LIVE_SPEC: Optional[perf_model.HardwareSpec] = None
_SPEC_EPOCH: int = 0


def _reset_spec_cache() -> None:  # test hook
    _SPEC_CACHE.clear()


def set_live_spec(spec: perf_model.HardwareSpec) -> int:
    """Install ``spec`` as the process-wide selection cost model and return
    the new spec epoch.  Takes effect for every subsequent `default_spec()`
    call across all tiers; previously jitted/cached computations keep the
    selection they were traced with (documented staleness — re-tracing picks
    up the new spec)."""
    global _LIVE_SPEC, _SPEC_EPOCH
    if not isinstance(spec, perf_model.HardwareSpec):
        raise TypeError(f"live spec must be a HardwareSpec, got {type(spec)}")
    _LIVE_SPEC = spec
    _SPEC_EPOCH += 1
    return _SPEC_EPOCH


def clear_live_spec() -> None:
    """Drop the live override; `default_spec()` reverts to the calibrated
    platform spec.  Bumps the epoch so decision caches refresh."""
    global _LIVE_SPEC, _SPEC_EPOCH
    if _LIVE_SPEC is not None:
        _LIVE_SPEC = None
        _SPEC_EPOCH += 1


def live_spec() -> Optional[perf_model.HardwareSpec]:
    """The installed live override, or None when untuned."""
    return _LIVE_SPEC


def spec_epoch() -> int:
    """Monotonic counter bumped on every live-spec install/clear.  Decision
    caches include it in their keys so spec swaps invalidate stale entries."""
    return _SPEC_EPOCH


def calibrated_spec() -> perf_model.HardwareSpec:
    """Platform spec ignoring any live-tuned override: on TPU the row of
    `perf_model.TPU_SPECS` for the device's `device_kind` (an unknown kind
    raises); on CPU the calibrated spec from `benchmarks/calibrate.py` when
    present (falling back to the priors).  This is the envelope anchor the tuning
    controller validates live proposals against."""
    backend = jax.default_backend()
    if backend in _SPEC_CACHE:
        return _SPEC_CACHE[backend]
    if backend == "tpu":
        spec = perf_model.tpu_spec(jax.devices()[0].device_kind)
    else:
        spec = perf_model.cpu_default_spec()
        path = calibrated_spec_path()
        try:
            import json
            import os
            if os.path.exists(path):
                with open(path) as f:
                    payload = json.load(f)
                if payload.get("jax_backend", backend) == backend:
                    spec = perf_model.spec_from_dict(
                        payload.get("spec", payload), base=spec)
        except (OSError, ValueError, KeyError, TypeError):
            pass  # unreadable calibration files must never break dispatch
    _SPEC_CACHE[backend] = spec
    return spec


def default_spec() -> perf_model.HardwareSpec:
    """The spec every selector tier uses when the caller passes none: the
    live-tuned override when a `repro.tuning.SpecController` has installed
    one, else the calibrated platform spec."""
    if _LIVE_SPEC is not None:
        return _LIVE_SPEC
    return calibrated_spec()


class Selection(NamedTuple):
    """A selector decision plus its predicted-cost record — what the
    telemetry layer persists so predicted-vs-measured drift can be
    tracked per tier (`repro.telemetry.drift`)."""

    choice: str                  # winning backend/strategy name
    predicted_s: float           # its predicted cost (the model's claim)
    costs: Dict[str, float]      # every candidate's prediction


def select_backend_with_cost(op: str, n: int, m: int,
                             spec: Optional[perf_model.HardwareSpec] = None,
                             *, uniform_expected: bool = True, dtype=None,
                             need_fetched: bool = True) -> Selection:
    """`select_backend` returning the full predicted-cost record."""
    if op not in OPS:
        raise ValueError(f"unknown op {op!r}")
    spec = spec or default_spec()
    costs = {b.name: b.cost(spec, op, n, m, need_fetched)
             for b in BACKENDS.values()
             if b.supports(op, uniform_expected=uniform_expected,
                           dtype=dtype, m=m, need_fetched=need_fetched)}
    choice = min(costs, key=costs.get)
    return Selection(choice, costs[choice], costs)


def select_backend(op: str, n: int, m: int,
                   spec: Optional[perf_model.HardwareSpec] = None, *,
                   uniform_expected: bool = True, dtype=None,
                   need_fetched: bool = True) -> str:
    """Cheapest backend whose semantics cover (op, expected-mode, dtype)."""
    return select_backend_with_cost(
        op, n, m, spec, uniform_expected=uniform_expected, dtype=dtype,
        need_fetched=need_fetched).choice


def _eager(*arrays) -> bool:
    """True when none of ``arrays`` is being traced (an eager call)."""
    return not any(isinstance(a, jax.core.Tracer) for a in arrays)


_NO_SPAN = contextlib.nullcontext()


def host_span(name: str, eager: bool, **fields):
    """A `telemetry.span` on an eager call; nothing while jit traces."""
    return telemetry.span(name, **fields) if eager else _NO_SPAN


def resolve_backend(table: Array, indices: Array, op: str,
                    expected: Optional[Array] = None, *,
                    backend: str = "auto",
                    spec: Optional[perf_model.HardwareSpec] = None,
                    need_fetched: bool = True) -> str:
    """The backend ``execute_backend`` runs: ``backend`` itself, or for
    "auto" the cost model's pick, inside the host span ``atomics.select``
    on an eager call (selection happens at trace time under jit)."""
    if backend != "auto":
        return backend
    with host_span("atomics.select", _eager(table, indices)):
        return select_backend(
            op, int(indices.shape[0]), int(table.shape[0]), spec,
            uniform_expected=(op != "cas") or _is_uniform_expected(expected),
            dtype=table.dtype, need_fetched=need_fetched)


def execute_backend(table: Array, indices: Array, values: Array, op: str,
                    expected: Optional[Array] = None, *,
                    backend: str = "auto",
                    spec: Optional[perf_model.HardwareSpec] = None,
                    need_fetched: bool = True) -> RmwResult:
    """Run an RMW batch on the named backend ("auto" = cost-model pick).

    The local tier of the unified front-end — call it through
    `repro.atomics.execute`; this raw-array spelling is the internal entry
    the sharded subsystem's pre-combine/resolve passes use.

    Shapes are static under jit, so auto-selection happens at trace time and
    costs nothing at runtime.  All backends return the serialized-equivalent
    :class:`~repro.core.rmw.RmwResult`.  On an eager call the pick is the
    host span ``atomics.select`` and the call into the jitted backend, up
    to its return, the span ``atomics.dispatch``.

    ``need_fetched=False`` declares that the caller consumes only ``.table``
    (for CAS, also not ``.success``): backends may then skip the per-op
    fetch-result machinery (the one-hot backend degenerates to a single
    bincount-style scatter pass) and the returned fetched/success fields are
    unspecified.
    """
    if op not in OPS:
        raise ValueError(f"unknown op {op!r}")
    if op == "cas" and expected is None:
        raise ValueError("cas requires `expected`")
    backend = resolve_backend(table, indices, op, expected, backend=backend,
                              spec=spec, need_fetched=need_fetched)
    try:
        b = BACKENDS[backend]
    except KeyError:
        raise ValueError(f"unknown backend {backend!r}; "
                         f"have {sorted(BACKENDS)}") from None
    if not b.supports(op, m=int(table.shape[0]), need_fetched=need_fetched):
        raise ValueError(
            f"backend {b.name!r} does not run {op!r} on a "
            f"{int(table.shape[0])}-slot table with need_fetched="
            f"{need_fetched}")
    if op == "cas" and not b.general_cas \
            and not _is_uniform_expected(expected):
        raise ValueError(
            f"backend {b.name!r} supports CAS only with a scalar (uniform) "
            f"`expected`; per-op expected arrays need the serialized oracle")
    with host_span("atomics.dispatch", _eager(table, indices),
                    backend=backend):
        return b.run(table, indices, values, op, expected,
                     need_fetched=need_fetched)
