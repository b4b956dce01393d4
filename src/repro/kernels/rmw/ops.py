"""jit'd public wrappers for the combining-RMW kernel.

Handles padding (table to the tile multiple, batch to the block multiple)
and dtype management.  The kernels pick their mode from the platform: on
TPU the Mosaic kernel runs compiled; elsewhere the Pallas interpreter
executes the same kernel body (the CPU tests' validation mode).

`rmw_apply` returns the updated table only; `rmw_apply_fetched` additionally
returns per-op serialized-order fetch results and CAS success flags — this is
the entry the RMW engine's ``pallas`` backend (`core.rmw_engine`) calls.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.rmw import RmwResult
from repro.kernels.rmw import kernel as _k
from repro.kernels.rmw import ref as _ref

Array = jax.Array


def _pad_to(x: Array, multiple: int, fill) -> Array:
    n = x.shape[0]
    rem = (-n) % multiple
    if rem == 0:
        return x
    return jnp.concatenate([x, jnp.full((rem,), fill, x.dtype)])


@functools.partial(jax.jit, static_argnames=("op", "table_tile", "block",
                                             "use_kernel"))
def rmw_apply(table: Array, indices: Array, values: Array, op: str = "faa",
              *, table_tile: int = _k.DEFAULT_TABLE_TILE,
              block: int = _k.DEFAULT_BLOCK, use_kernel: bool = True) -> Array:
    """Combining-RMW a batch into a 1-D table.  Returns the updated table.

    Out-of-range indices are dropped (padding / masked tokens use index = n).
    """
    if not use_kernel:
        return _ref.rmw_table_ref(table, indices, values, op)
    n = table.shape[0]
    values = values.astype(table.dtype)
    tab_p = _pad_to(table, table_tile, 0)
    # padded table slots must not capture ops: point padding indices past even
    # the padded table
    idx_p = _pad_to(indices.astype(jnp.int32), block, jnp.int32(tab_p.shape[0]))
    val_p = _pad_to(values, block, 0)
    out = _k.rmw_table(tab_p, idx_p, val_p, op, table_tile=table_tile,
                       block=block)
    return out[:n]


@functools.partial(jax.jit, static_argnames=("op", "table_tile", "block"))
def rmw_apply_fetched(table: Array, indices: Array, values: Array,
                      op: str = "faa", *, expected: Optional[Array] = None,
                      table_tile: int = _k.DEFAULT_TABLE_TILE,
                      block: int = _k.DEFAULT_BLOCK) -> RmwResult:
    """Combining RMW with per-op fetched values (and CAS success flags).

    Pads like :func:`rmw_apply`; fetched/success are sliced back to the
    caller's batch.  Out-of-range indices are dropped (fetched 0, success
    False).  CAS takes one uniform ``expected`` value.
    """
    n = table.shape[0]
    n_ops = indices.shape[0]
    values = values.astype(table.dtype)
    tab_p = _pad_to(table, table_tile, 0)
    # out-of-range ops must not observe table-padding slots: route them (and
    # the batch padding) past even the padded table so no one-hot row matches
    idx = indices.astype(jnp.int32)
    idx = jnp.where((idx < 0) | (idx >= n), jnp.int32(tab_p.shape[0]), idx)
    idx_p = _pad_to(idx, block, jnp.int32(tab_p.shape[0]))
    val_p = _pad_to(values, block, 0)
    out, fetched, success = _k.rmw_table_fetched(
        tab_p, idx_p, val_p, op, expected=expected, table_tile=table_tile,
        block=block)
    return RmwResult(out[:n], fetched[:n_ops], success[:n_ops])


def histogram(indices: Array, num_bins: int, **kw) -> Array:
    """Expert-load histogram — FAA with unit values (MoE routing's counter)."""
    return rmw_apply(jnp.zeros((num_bins,), jnp.float32), indices,
                     jnp.ones(indices.shape, jnp.float32), "faa", **kw)


@functools.partial(jax.jit, static_argnames=("m", "table_tile", "block"))
def slot_occupancy(indices: Array, m: int, *,
                   table_tile: int = _k.DEFAULT_TABLE_TILE,
                   block: int = _k.DEFAULT_BLOCK) -> Array:
    """(m,) int32 exact per-slot occupancy via the counters kernel output ref.

    Integer-exact companion of :func:`histogram` (whose fp32 FAA path would
    lose counts past 2^24): the contention observatory's occupancy source
    when the ``pallas`` backend executed the batch.  Same padding/drop
    contract as :func:`rmw_apply`.
    """
    tile = min(table_tile, max(128, ((m + 127) // 128) * 128))
    m_p = ((m + tile - 1) // tile) * tile
    idx = indices.astype(jnp.int32)
    idx = jnp.where((idx < 0) | (idx >= m), jnp.int32(m_p), idx)
    idx_p = _pad_to(idx, block, jnp.int32(m_p))
    out = _k.slot_counts(idx_p, m_p, table_tile=tile, block=block)
    return out[:m]
