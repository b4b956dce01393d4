"""Pallas TPU kernel: combining RMW (the paper's atomics, MXU-native).

TPU adaptation (DESIGN.md §2): a batch of atomic RMWs against a table is
re-expressed as a **one-hot matmul reduction** so that the combine runs on the
MXU/VPU instead of serializing, realizing the paper's proposed relaxed
atomics (§6.2.3).  For a table tile T (kept in VMEM across the inner grid
axis) and an index/value block B:

    one_hot[b, t] = (indices[b] == tile_start + t)
    faa:  tile += values @ one_hot              (1xB @ BxT matmul -> MXU)
    min/max: tile = combine(tile, masked col-reduce of values)
    swp:  tile = value of the *latest* collider per slot (last-wins)
    cas:  tile = first value != expected per live slot (uniform expected)

Grid = (table_tiles, index_blocks); the index-block axis is the reduction
("arbitrary") axis, the table-tile axis is parallel.  The index/value blocks
stream HBM->VMEM once per table tile; the table tile stays resident in VMEM —
this is the paper's Eq. (10) amortization with the VMEM tile in the
cache-line role.

**Fetched values** (`rmw_table_fetched`, used by the engine's `pallas`
backend): each op's serialized-order fetch result is the carried tile value
combined with the *exclusive per-slot prefix* of earlier colliders in its
block, computed as a strict-lower-triangular-masked one-hot contraction
``(L ∘ (oh @ oh^T)) @ v`` — another MXU matmul, no sort.  The tile axis
lives OUTSIDE the grid (one ``pallas_call`` per table tile, 1-D grid over
index blocks): each op's index lands in exactly one tile, so the disjoint
per-tile fetched/success contributions sum outside the kernel, and no
output block is ever revisited non-consecutively (the only revisit is the
tile accumulator along the single grid axis — the reduction pattern
compiled Pallas TPU guarantees).

``interpret=None`` (the default) compiles on TPU and interprets elsewhere.

Alignment: TABLE_TILE is a multiple of 128 (lane width) — the benchmark
`benchmarks/unaligned.py` measures the penalty of violating this, the TPU
analogue of the paper's §5.7 line-spanning atomics.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

DEFAULT_TABLE_TILE = 512      # table slots per tile (multiple of 128)
DEFAULT_BLOCK = 1024          # index/value elements per block
#: every one-hot contraction is exact in float32: one bf16 MXU pass would
#: round table values and sums past 2^8
_EXACT = jax.lax.Precision.HIGHEST


def _rmw_kernel(idx_ref, val_ref, table_ref, out_ref, *, op: str,
                table_tile: int, block: int):
    tile_id = pl.program_id(0)
    blk_id = pl.program_id(1)

    # Initialize the output tile from the input table on the first block.
    @pl.when(blk_id == 0)
    def _init():
        out_ref[...] = table_ref[...]

    tile_start = tile_id * table_tile
    idx = idx_ref[...].astype(jnp.int32)            # (1, block)
    val = val_ref[...]                              # (1, block)
    slots = jax.lax.broadcasted_iota(jnp.int32, (block, table_tile), 1)
    local = idx.reshape(block, 1) - tile_start
    one_hot = (local == slots)                      # (block, table_tile)

    acc = out_ref[...]                              # (1, table_tile)
    if op == "faa":
        # MXU path: (1, block) @ (block, tile) — the combining reduction.
        upd = jnp.dot(val, one_hot.astype(val.dtype),
                      preferred_element_type=jnp.float32,
                      precision=_EXACT)
        out_ref[...] = acc + upd.astype(acc.dtype)
    elif op in ("min", "max"):
        neutral = (jnp.asarray(jnp.finfo(val.dtype).max, val.dtype) if op == "min"
                   else jnp.asarray(jnp.finfo(val.dtype).min, val.dtype))
        masked = jnp.where(one_hot, val.reshape(block, 1), neutral)
        red = (jnp.min(masked, axis=0) if op == "min"
               else jnp.max(masked, axis=0)).reshape(1, table_tile)
        comb = jnp.minimum if op == "min" else jnp.maximum
        out_ref[...] = comb(acc, red)
    elif op == "swp":
        # last-wins: the collider with the highest global batch position.
        pos = jax.lax.broadcasted_iota(jnp.int32, (block, table_tile), 0) \
            + blk_id * block
        masked_pos = jnp.where(one_hot, pos, -1)
        best = jnp.max(masked_pos, axis=0).reshape(1, table_tile)  # (1, tile)
        # gather the winning value via a second one-hot contraction
        sel = (masked_pos == best) & one_hot & (best >= 0)
        winner = jnp.dot(val, sel.astype(val.dtype),
                         preferred_element_type=jnp.float32,
                         precision=_EXACT)
        out_ref[...] = jnp.where(best >= 0, winner.astype(acc.dtype), acc)
    else:
        raise ValueError(f"unknown op {op!r}")


@functools.partial(jax.jit,
                   static_argnames=("op", "table_tile", "block", "interpret"))
def rmw_table(table: jax.Array, indices: jax.Array, values: jax.Array,
              op: str = "faa", *, table_tile: int = DEFAULT_TABLE_TILE,
              block: int = DEFAULT_BLOCK,
              interpret: Optional[bool] = None) -> jax.Array:
    """Apply a combining-RMW batch to a 1-D fp32 table.

    Requires table size % table_tile == 0 and batch % block == 0 (ops.py pads).
    Out-of-range indices never match a slot and are dropped (mask tokens).
    ``interpret=None`` auto-selects from the platform.
    """
    n = table.shape[0]
    nb = indices.shape[0]
    assert n % table_tile == 0, (n, table_tile)
    assert nb % block == 0, (nb, block)
    grid = (n // table_tile, nb // block)

    kernel = functools.partial(_rmw_kernel, op=op, table_tile=table_tile,
                               block=block)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block), lambda t, b: (0, b)),       # indices
            pl.BlockSpec((1, block), lambda t, b: (0, b)),       # values
            pl.BlockSpec((1, table_tile), lambda t, b: (0, t)),  # table in
        ],
        out_specs=pl.BlockSpec((1, table_tile), lambda t, b: (0, t)),
        out_shape=jax.ShapeDtypeStruct((1, n), table.dtype),
        interpret=resolve_interpret(interpret),
    )(indices.reshape(1, nb), values.reshape(1, nb), table.reshape(1, n))
    return out.reshape(n)


# ---------------------------------------------------------------------------
# Contention counters kernel (PR 10 observatory)
# ---------------------------------------------------------------------------

def _slot_count_kernel(idx_ref, count_ref, *, table_tile: int, block: int):
    """Per-slot occupancy counts via the same one-hot contraction as the RMW.

    The counters output ref accumulates column sums of the one-hot matrix
    across index blocks — the combine pass's collision counts emitted as a
    first-class output instead of being discarded after the reduction.
    """
    tile_id = pl.program_id(0)
    blk_id = pl.program_id(1)

    @pl.when(blk_id == 0)
    def _init():
        count_ref[...] = jnp.zeros_like(count_ref)

    tile_start = tile_id * table_tile
    idx = idx_ref[...].astype(jnp.int32)            # (1, block)
    slots = jax.lax.broadcasted_iota(jnp.int32, (block, table_tile), 1)
    local = idx.reshape(block, 1) - tile_start
    one_hot = (local == slots)                      # (block, table_tile)
    upd = jnp.sum(one_hot.astype(jnp.int32), axis=0).reshape(1, table_tile)
    count_ref[...] = count_ref[...] + upd


@functools.partial(jax.jit,
                   static_argnames=("m", "table_tile", "block", "interpret"))
def slot_counts(indices: jax.Array, m: int, *,
                table_tile: int = DEFAULT_TABLE_TILE,
                block: int = DEFAULT_BLOCK,
                interpret: Optional[bool] = None) -> jax.Array:
    """(m,) int32 occupancy counts for a slot-index batch.

    Same padding contract as `rmw_table`: m % table_tile == 0 and
    batch % block == 0 (ops.py pads); out-of-range indices match no slot.
    """
    nb = indices.shape[0]
    assert m % table_tile == 0, (m, table_tile)
    assert nb % block == 0, (nb, block)
    grid = (m // table_tile, nb // block)

    kernel = functools.partial(_slot_count_kernel, table_tile=table_tile,
                               block=block)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((1, block), lambda t, b: (0, b))],
        out_specs=pl.BlockSpec((1, table_tile), lambda t, b: (0, t)),
        out_shape=jax.ShapeDtypeStruct((1, m), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(indices.reshape(1, nb))
    return out.reshape(m)


# ---------------------------------------------------------------------------
# Fetched-value kernel (serialized-order fetch results + uniform-expected CAS)
# ---------------------------------------------------------------------------

def _rmw_fetched_kernel(idx_ref, val_ref, table_ref, exp_ref, out_ref,
                        fetched_ref, success_ref, *, op: str,
                        table_tile: int, block: int, tile_start: int):
    # 1-D grid over index blocks; the table tile this call owns is fixed
    # (``tile_start`` is static — the tile axis lives OUTSIDE the grid, one
    # pallas_call per tile).  This keeps every output block's revisit pattern
    # within what compiled Pallas TPU guarantees: the table-tile out block is
    # constant across the (only) grid axis — the standard minor-axis
    # reduction — and each fetched/success block is written exactly once.
    blk_id = pl.program_id(0)

    @pl.when(blk_id == 0)
    def _init_tile():
        out_ref[...] = table_ref[...]

    idx = idx_ref[...].astype(jnp.int32)            # (1, block)
    val = val_ref[...]                              # (1, block)
    slots = jax.lax.broadcasted_iota(jnp.int32, (block, table_tile), 1)
    local = idx.reshape(block, 1) - tile_start
    one_hot = (local == slots)                      # (block, table_tile)
    in_tile = (idx >= tile_start) & (idx < tile_start + table_tile)  # (1, B)

    acc = out_ref[...]                              # tile BEFORE this block
    ohf = one_hot.astype(val.dtype)
    # base[i] = acc[idx[i]] — gather as a one-hot contraction (MXU)
    base = jnp.dot(acc, ohf.T, preferred_element_type=jnp.float32,
                   precision=_EXACT).astype(val.dtype)   # (1, block)

    pos_i = jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
    pos_j = jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
    # strict-lower-triangular same-slot mask: j precedes i, same table slot.
    # (equality on idx restricted to this tile via the row mask below)
    same = (idx.reshape(block, 1) == idx.reshape(1, block)) & (pos_i > pos_j)

    ones = jnp.ones((1, block), val.dtype)
    if op == "faa":
        # exclusive per-slot prefix: the lower-triangular-masked one-hot matmul
        prefix = jnp.dot(val, same.astype(val.dtype).T,
                         preferred_element_type=jnp.float32,
                         precision=_EXACT).astype(val.dtype)
        fetched = base + prefix
        ok = ones
        upd = jnp.dot(val, ohf, preferred_element_type=jnp.float32,
                      precision=_EXACT)
        out_ref[...] = acc + upd.astype(acc.dtype)
    elif op in ("min", "max"):
        neutral = (jnp.asarray(jnp.finfo(val.dtype).max, val.dtype)
                   if op == "min"
                   else jnp.asarray(jnp.finfo(val.dtype).min, val.dtype))
        comb = jnp.minimum if op == "min" else jnp.maximum
        masked = jnp.where(same, val.reshape(1, block), neutral)   # (B, B)
        prefix = (jnp.min(masked, axis=1) if op == "min"
                  else jnp.max(masked, axis=1)).reshape(1, block)
        fetched = comb(base, prefix)
        ok = ones
        colmask = jnp.where(one_hot, val.reshape(block, 1), neutral)
        red = (jnp.min(colmask, axis=0) if op == "min"
               else jnp.max(colmask, axis=0)).reshape(1, table_tile)
        out_ref[...] = comb(acc, red)
    elif op == "swp":
        mpos = jnp.where(same, pos_j, -1).max(axis=1).reshape(1, block)
        sel = same & (pos_j == mpos.reshape(block, 1))
        prev = jnp.dot(val, sel.astype(val.dtype).T,
                       preferred_element_type=jnp.float32,
                       precision=_EXACT).astype(val.dtype)
        fetched = jnp.where(mpos >= 0, prev, base)
        ok = ones
        gpos = jax.lax.broadcasted_iota(jnp.int32, (block, table_tile), 0) \
            + blk_id * block
        masked_pos = jnp.where(one_hot, gpos, -1)
        best = jnp.max(masked_pos, axis=0).reshape(1, table_tile)
        wsel = (masked_pos == best) & one_hot & (best >= 0)
        winner = jnp.dot(val, wsel.astype(val.dtype),
                         preferred_element_type=jnp.float32,
                         precision=_EXACT)
        out_ref[...] = jnp.where(best >= 0, winner.astype(acc.dtype), acc)
    else:  # cas (uniform expected): first value != expected wins a live slot
        e = exp_ref[0, 0].astype(val.dtype)
        ne = val != e                                              # (1, B)
        # column form from the values, not from `ne`: Mosaic cannot
        # reshape a boolean vector (1, B) -> (B, 1)
        ne_col = val.reshape(block, 1) != e                        # (B, 1)
        big = jnp.int32(block)
        fpos = jnp.where(same & ne, pos_j, big
                         ).min(axis=1).reshape(1, block)
        xsel = same & ne & (pos_j == fpos.reshape(block, 1))
        xval = jnp.dot(val, xsel.astype(val.dtype).T,
                       preferred_element_type=jnp.float32,
                       precision=_EXACT).astype(val.dtype)
        x_excl = jnp.where(fpos < big, xval, e)
        v_before = jnp.where(base == e, x_excl, base)
        fetched = v_before
        ok = (v_before == e).astype(val.dtype)
        # tile update: per slot, the first op with value != expected
        opos = jax.lax.broadcasted_iota(jnp.int32, (block, table_tile), 0)
        fslot = jnp.where(one_hot & ne_col, opos, big
                          ).min(axis=0).reshape(1, table_tile)
        fsel = one_hot & (opos == fslot) & ne_col
        first_val = jnp.dot(val, fsel.astype(val.dtype),
                            preferred_element_type=jnp.float32,
                            precision=_EXACT
                            ).astype(acc.dtype)
        out_ref[...] = jnp.where((acc == e) & (fslot < big), first_val, acc)

    # each op's index lives in exactly one tile: this call's contribution is
    # zero elsewhere, and the caller sums the per-tile outputs.
    itf = in_tile.astype(val.dtype)
    fetched_ref[...] = (fetched * itf).astype(fetched_ref.dtype)
    success_ref[...] = (ok * itf).astype(success_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("op", "table_tile", "block", "interpret"))
def rmw_table_fetched(table: jax.Array, indices: jax.Array,
                      values: jax.Array, op: str = "faa", *,
                      expected: Optional[jax.Array] = None,
                      table_tile: int = DEFAULT_TABLE_TILE,
                      block: int = DEFAULT_BLOCK,
                      interpret: Optional[bool] = None):
    """Combining RMW returning ``(table, fetched, success)``.

    Semantics match `core.rmw.rmw_serialized` per-op fetch results; CAS takes
    one uniform ``expected`` value (the combinable form).  Out-of-range
    indices are dropped: fetched = 0, success = False for those ops.
    Alignment contract as :func:`rmw_table` (ops.py pads).

    One ``pallas_call`` per table tile, each with a 1-D grid over index
    blocks (the tile stays VMEM-resident for the whole sweep); per-tile
    fetched/success contributions are disjoint and summed outside the
    kernel.  This costs one launch per tile but never revisits an output
    block non-consecutively — the pattern compiled Pallas TPU supports.
    """
    n = table.shape[0]
    nb = indices.shape[0]
    assert n % table_tile == 0, (n, table_tile)
    assert nb % block == 0, (nb, block)
    if op == "cas" and expected is None:
        raise ValueError("cas requires `expected`")
    interp = resolve_interpret(interpret)
    exp = jnp.full((1, 1), 0 if expected is None else expected, table.dtype)
    idx2 = indices.reshape(1, nb)
    val2 = values.reshape(1, nb)
    tab2 = table.reshape(1, n)

    out_tiles = []
    fetched = jnp.zeros((1, nb), table.dtype)
    success = jnp.zeros((1, nb), table.dtype)
    for ti in range(n // table_tile):
        kernel = functools.partial(_rmw_fetched_kernel, op=op,
                                   table_tile=table_tile, block=block,
                                   tile_start=ti * table_tile)
        out_t, f_t, s_t = pl.pallas_call(
            kernel,
            grid=(nb // block,),
            in_specs=[
                pl.BlockSpec((1, block), lambda b: (0, b)),       # indices
                pl.BlockSpec((1, block), lambda b: (0, b)),       # values
                pl.BlockSpec((1, table_tile), lambda b: (0, 0)),  # table tile
                pl.BlockSpec((1, 1), lambda b: (0, 0)),           # expected
            ],
            out_specs=[
                pl.BlockSpec((1, table_tile), lambda b: (0, 0)),  # tile out
                pl.BlockSpec((1, block), lambda b: (0, b)),       # fetched
                pl.BlockSpec((1, block), lambda b: (0, b)),       # success
            ],
            out_shape=[
                jax.ShapeDtypeStruct((1, table_tile), table.dtype),
                jax.ShapeDtypeStruct((1, nb), table.dtype),
                jax.ShapeDtypeStruct((1, nb), table.dtype),
            ],
            interpret=interp,
        )(idx2, val2, tab2[:, ti * table_tile:(ti + 1) * table_tile], exp)
        out_tiles.append(out_t)
        fetched = fetched + f_t
        success = success + s_t
    out = jnp.concatenate(out_tiles, axis=1)
    return out.reshape(n), fetched.reshape(nb), success.reshape(nb) > 0.5
