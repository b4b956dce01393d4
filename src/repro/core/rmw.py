"""Vectorized RMW (read-modify-write) with the paper's atomic semantics.

The paper benchmarks CAS / FAA / SWP — hardware-serialized RMWs on cache
lines.  The TPU has no hardware atomics; instead, a *batch* of RMWs against a
table is executed as a data-parallel **combine-by-index** whose results are
bit-identical to executing the batch serially in order (the paper's hardware
semantics).  This module provides:

* :func:`rmw_serialized` — the order-faithful oracle (``lax.scan``, one op per
  step) — models the paper's measured hardware behaviour (no ILP, §5.2).
* :func:`rmw_combining`  — the vectorized segmented-scan implementation — the
  paper's *proposed* relaxed atomics (§6.2.3) which TPUs realize in software.
  For FAA/SWP/MIN/MAX and for CAS with a uniform expected value it returns
  exactly the serialized result (property-tested in tests/test_rmw.py).

Shared helpers (`segmented_scan`, the sort-based arrival rank behind
`repro.atomics.arrival_rank`) are reused by the MoE dispatch
(position-in-expert counters = FAA fetch results) and the BFS example
(parent updates = CAS/SWP).

This module holds the *sort* (sort + segmented scan) implementation and
the serialized oracle — implementation building blocks for the engine
(`core.rmw_engine`) and the unified front-end (`repro.atomics`, the one
public entry).  The PR-3 deprecation shims (the ``rmw()`` facade and the
argsort ``arrival_rank`` spelling) completed their one-release window and
are gone; `repro.atomics.execute` / `repro.atomics.arrival_rank` are the
public spellings.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

Array = jax.Array

OPS = ("faa", "swp", "cas", "min", "max")


class RmwResult(NamedTuple):
    table: Array    # table after all ops applied
    fetched: Array  # per-op value observed *before* that op (serialized order)
    success: Array  # per-op bool; always True for non-CAS ops


# ---------------------------------------------------------------------------
# Segmented scan machinery (the classic (flag, value) monoid)
# ---------------------------------------------------------------------------

#: longest array one ``lax.associative_scan`` covers in `segmented_scan`.
#: The TPU compiler's time grows much faster than the length: about 2 s for
#: 2^16 elements, about 110 s for 2^20 (compiled for a v5e).  Longer arrays
#: run as a sequential loop over blocks of this size.
SCAN_BLOCK = 1 << 16


def segmented_scan(values: Array, seg_start: Array,
                   combine: Callable[[Array, Array], Array]) -> Array:
    """Inclusive segmented scan of a 1-D array: scans ``values`` with
    ``combine`` but restarts at every True in ``seg_start``.  Associative,
    so each block of up to `SCAN_BLOCK` elements is one log-depth
    ``lax.associative_scan`` (the 'relaxed atomics' fast path); the carry
    passes from block to block by ``lax.scan``."""

    def op(a, b):
        fa, va = a
        fb, vb = b
        return fa | fb, jnp.where(fb, vb, combine(va, vb))

    n = values.shape[0]
    blk = min(n, SCAN_BLOCK)
    # element 0 starts a segment whatever its flag says, so the initial
    # carry (no identity is known for ``combine``) is never combined
    flags = seg_start.astype(bool).at[0].set(True)
    pad = (-n) % blk                 # trailing pad: no real element sees it
    fb = jnp.pad(flags, (0, pad), constant_values=True).reshape(-1, blk)
    vb = jnp.pad(values, (0, pad)).reshape(-1, blk)

    def block(carry, xs):
        local = jax.lax.associative_scan(op, xs)
        f, v = op(carry, local)
        return (f[-1], v[-1]), v

    carry0 = (jnp.array(False), jnp.zeros((), values.dtype))
    _, out = jax.lax.scan(block, carry0, (fb, vb))
    return out.reshape(-1)[:n]


def _exclusive_from_inclusive(incl: Array, values: Array, seg_start: Array,
                              identity) -> Array:
    """Shift an inclusive segmented scan to exclusive (identity at seg starts)."""
    shifted = jnp.roll(incl, 1, axis=0)
    first = jnp.zeros_like(seg_start).at[0].set(True) | seg_start
    return jnp.where(first, jnp.asarray(identity, incl.dtype), shifted)


def _sort_by_index(indices: Array, *payloads: Array):
    """Stable sort of the batch by slot, the payloads riding along.

    One ``lax.sort`` carries the payloads and the arrival order, so no
    permutation is applied by a gather: on the TPU a random gather of the
    batch costs several sorts of it."""
    iota = jnp.arange(indices.shape[0], dtype=jnp.int32)
    idx_s, order, *sorted_payloads = jax.lax.sort(
        (indices, iota, *payloads), num_keys=1, is_stable=True)
    seg_start = jnp.concatenate(
        [jnp.ones((1,), bool), idx_s[1:] != idx_s[:-1]])
    return order, idx_s, seg_start, tuple(sorted_payloads)


def _unsort(order: Array, *arrays: Array):
    """Put arrays in sorted order back in arrival order: a sort keyed on
    ``order``, a permutation, so its keys are unique."""
    return tuple(jax.lax.sort((order, *arrays), num_keys=1)[1:])


def _arrival_rank_argsort(keys: Array) -> Array:
    """Per-element arrival order among equal keys (0-based), via a sort.

    Semantically this is the fetch result of FAA(counter[key], 1) executed in
    element order — the exact primitive MoE dispatch uses to assign each token
    its slot within its expert's capacity buffer.  The sort-free version
    lives in the engine; `repro.atomics.arrival_rank` is the one public
    spelling (this path is its ``num_keys=None`` fallback).
    """
    order, _, seg_start, _ = _sort_by_index(keys)
    ones = jnp.ones_like(keys, dtype=jnp.int32)
    incl = segmented_scan(ones, seg_start, jnp.add)
    (rank,) = _unsort(order, incl - 1)
    return rank


# ---------------------------------------------------------------------------
# Serialized oracle (paper hardware: one atomic at a time)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("op",))
def rmw_serialized(table: Array, indices: Array, values: Array, op: str,
                   expected: Optional[Array] = None) -> RmwResult:
    """Apply ops one-at-a-time in order; the semantics oracle.

    This is also the performance model of the *paper's measured hardware*:
    fully serialized execution with zero ILP between atomics (§5.2).
    """
    if op not in OPS:
        raise ValueError(f"unknown op {op!r}")
    if op == "cas" and expected is None:
        raise ValueError("cas requires `expected`")
    # a scalar (uniform) expected applies to every op
    exp = (jnp.zeros_like(values) if expected is None else
           jnp.broadcast_to(jnp.asarray(expected, values.dtype),
                            values.shape))

    def step(tab, inp):
        i, v, e = inp
        old = tab[i]
        if op == "faa":
            new, ok = old + v, jnp.array(True)
        elif op == "swp":
            new, ok = v, jnp.array(True)
        elif op == "min":
            new, ok = jnp.minimum(old, v), jnp.array(True)
        elif op == "max":
            new, ok = jnp.maximum(old, v), jnp.array(True)
        else:  # cas
            ok = old == e
            new = jnp.where(ok, v, old)
        return tab.at[i].set(new), (old, ok)

    table, (fetched, success) = jax.lax.scan(step, table, (indices, values, exp))
    return RmwResult(table, fetched, success)


# ---------------------------------------------------------------------------
# Combining implementation (the paper's proposed relaxed atomics, vectorized)
# ---------------------------------------------------------------------------

def _combine_fn(op: str):
    return {"faa": jnp.add, "min": jnp.minimum, "max": jnp.maximum}[op]


def _identity(op: str, dtype):
    if op == "faa":
        return jnp.zeros((), dtype)
    if op == "min":
        return jnp.array(jnp.iinfo(dtype).max if jnp.issubdtype(dtype, jnp.integer)
                         else jnp.inf, dtype)
    return jnp.array(jnp.iinfo(dtype).min if jnp.issubdtype(dtype, jnp.integer)
                     else -jnp.inf, dtype)


@partial(jax.jit, static_argnames=("op",))
def rmw_combining(table: Array, indices: Array, values: Array, op: str,
                  expected: Optional[Array] = None) -> RmwResult:
    """Vectorized RMW batch, serialized-equivalent results.

    FAA/MIN/MAX: fetched = table ⊕ (exclusive segmented scan of colliders);
    SWP: fetched = previous collider's value (or the table value for the first);
    CAS: supported for a *uniform* expected value (first-wins within a segment)
    — the BFS/dispatch pattern; general per-op expected falls back to the
    serialized oracle (the paper's 'wasted work' case cannot be combined).
    """
    if op not in OPS:
        raise ValueError(f"unknown op {op!r}")
    n = indices.shape[0]
    if op == "cas":
        if expected is None:
            raise ValueError("cas requires `expected`")
        # Uniform-expected CAS is combinable; otherwise use the oracle.
        return _cas_uniform(table, indices, values, expected)

    with jax.named_scope("rmw.sort"):
        order, idx_s, seg_start, (val_s,) = _sort_by_index(indices, values)
    with jax.named_scope("rmw.gather"):
        base = table[idx_s]

    if op == "swp":
        with jax.named_scope("rmw.scan"):
            prev = jnp.roll(val_s, 1, axis=0)
            fetched_s = jnp.where(seg_start, base, prev)
        with jax.named_scope("rmw.scatter"):
            # last-wins: route non-final writes to a scratch row
            is_end = jnp.concatenate([seg_start[1:], jnp.ones((1,), bool)])
            scratch = jnp.asarray(table.shape[0], idx_s.dtype)
            write_idx = jnp.where(is_end, idx_s, scratch)
            padded = jnp.concatenate([table, table[:1]], axis=0)
            new_table = padded.at[write_idx].set(val_s)[:-1]
        with jax.named_scope("rmw.unsort"):
            (fetched,) = _unsort(order, fetched_s)
        return RmwResult(new_table, fetched, jnp.ones((n,), bool))

    comb = _combine_fn(op)
    with jax.named_scope("rmw.scan"):
        incl = segmented_scan(val_s, seg_start, comb)
        exc = _exclusive_from_inclusive(incl, val_s, seg_start,
                                        _identity(op, values.dtype))
        fetched_s = comb(base, exc) if op != "faa" else base + exc
    with jax.named_scope("rmw.scatter"):
        if op == "faa":
            new_table = table.at[indices].add(values)
        elif op == "min":
            new_table = table.at[indices].min(values)
        else:
            new_table = table.at[indices].max(values)
    with jax.named_scope("rmw.unsort"):
        (fetched,) = _unsort(order, fetched_s)
    return RmwResult(new_table, fetched, jnp.ones((n,), bool))


def _cas_uniform(table: Array, indices: Array, values: Array,
                 expected: Array) -> RmwResult:
    """CAS with one shared expected value: first collider at a matching slot
    wins; later colliders observe the winner's value and fail (paper's BFS
    pattern: cas(parent[v], -1, u)).  1-D tables only."""
    exp = jnp.asarray(expected, table.dtype)
    with jax.named_scope("rmw.sort"):
        order, idx_s, seg_start, (val_s,) = _sort_by_index(indices, values)
    with jax.named_scope("rmw.gather"):
        base = table[idx_s]
    with jax.named_scope("rmw.scan"):
        matches = base == exp  # slot held `expected` before the batch
        # Serialized chain semantics: ops succeed while the slot still
        # holds `expected`.  Writing desired == expected keeps the chain
        # alive; the first op writing desired != expected ("break op")
        # ends it.
        eq = (val_s == exp).astype(jnp.int32)
        incl_alive = segmented_scan(eq, seg_start, jnp.minimum)
        alive_excl = _exclusive_from_inclusive(incl_alive, eq, seg_start, 1
                                               ).astype(bool)
        success_s = matches & alive_excl
        break_op = success_s & (eq == 0)
        contrib = jnp.where(break_op, val_s, jnp.zeros_like(val_s))
        incl_break = segmented_scan(contrib, seg_start, jnp.add)
        break_excl = _exclusive_from_inclusive(incl_break, contrib,
                                               seg_start, 0)
        fetched_s = jnp.where(alive_excl | ~matches, base, break_excl)
    with jax.named_scope("rmw.scatter"):
        # Table write: only the break op changes the slot's value.
        scratch = jnp.asarray(table.shape[0], idx_s.dtype)
        write_idx = jnp.where(break_op, idx_s, scratch)
        padded = jnp.concatenate([table, table[:1]], axis=0)
        new_table = padded.at[write_idx].set(val_s)[:-1]
    with jax.named_scope("rmw.unsort"):
        fetched, success = _unsort(order, fetched_s, success_s)
    return RmwResult(new_table, fetched, success)


def scatter_add_grads(grad_table: Array, token_ids: Array,
                      grads: Array) -> Array:
    """Embedding-gradient accumulation = a pure-FAA RMW batch (dense archs'
    use of the paper technique; DESIGN.md §5)."""
    return grad_table.at[token_ids].add(grads)
