"""Pallas TPU kernel: per-chunk SSD block (the quadratic hot spot).

The SSD chunked algorithm splits the sequence into chunks of length Q.  The
*within-chunk* work is attention-shaped (two (Q,N)/(Q,P) matmuls through a
decay-masked (Q,Q) score matrix — MXU work) and is what this kernel computes;
the *cross-chunk* state recurrence is a cheap log-depth associative scan done
in jnp by ops.py.

Per grid cell (one batch-head, one chunk) the kernel emits:
  y_intra (Q,P)  — contribution of in-chunk tokens,
  state  (N,P)   — this chunk's end-state contribution  Σ_s exp(lQ-l_s)·dt_s·B_s⊗x_s
All inputs are pre-scaled by ops.py: xdt = x*dt, adt = A*dt.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

DEFAULT_CHUNK = 128


def _ssd_kernel(xdt_ref, adt_row_ref, adt_col_ref, b_ref, c_ref, y_ref,
                state_ref, *, chunk: int):
    xdt = xdt_ref[0].astype(jnp.float32)          # (Q, P)
    adt_row = adt_row_ref[0].astype(jnp.float32)  # (1, Q)
    adt_col = adt_col_ref[0].astype(jnp.float32)  # (Q, 1)
    bmat = b_ref[0].astype(jnp.float32)           # (Q, N)
    cmat = c_ref[0].astype(jnp.float32)           # (Q, N)

    # inclusive cumsum l[t] = sum_{k<=t} adt[k] as masked lower-triangular
    # reductions (Mosaic has no cumsum), once per layout: l_col[t] sums
    # row t of the mask, l_row[s] sums column s
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    lower = cols <= rows                                   # [t, s]: s <= t
    l_col = jnp.sum(jnp.where(lower, adt_row, 0.0), axis=1,
                    keepdims=True)                         # (Q, 1)
    l_row = jnp.sum(jnp.where(rows <= cols, adt_col, 0.0), axis=0,
                    keepdims=True)                         # (1, Q)
    # decay mask M[t, s] = exp(l_t - l_s) for s <= t else 0
    m = jnp.where(lower, jnp.exp(l_col - l_row), 0.0)     # (Q, Q)

    scores = jax.lax.dot_general(cmat, bmat, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # (Q,Q)
    y_ref[0] = jax.lax.dot_general(scores * m, xdt,
                                   (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32
                                   ).astype(y_ref.dtype)

    # chunk state: B^T @ (xdt * exp(l_Q - l_s))
    decay_to_end = jnp.exp(jnp.sum(adt_row) - l_col)      # (Q, 1)
    state_ref[0, 0] = jax.lax.dot_general(
        bmat, xdt * decay_to_end, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(state_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_chunk(xdt: jax.Array, adt: jax.Array, B: jax.Array, C: jax.Array, *,
              chunk: int = DEFAULT_CHUNK,
              interpret: Optional[bool] = None
              ) -> tuple[jax.Array, jax.Array]:
    """Compute per-chunk intra outputs and chunk states.

    xdt (BH, S, P), adt (BH, S), B/C (BH, S, N); S % chunk == 0.
    Returns y_intra (BH, S, P), states (BH, NC, N, P).
    ``interpret=None`` compiles on TPU and interprets elsewhere.
    """
    bh, s, p = xdt.shape
    n = B.shape[-1]
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    y, states = pl.pallas_call(
        kernel,
        grid=(bh, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, p), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, 1, chunk), lambda i, c: (i, 0, c)),
            pl.BlockSpec((1, chunk, 1), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, chunk, n), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, chunk, n), lambda i, c: (i, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, p), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, 1, n, p), lambda i, c: (i, c, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, p), jnp.float32),
            jax.ShapeDtypeStruct((bh, nc, n, p), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(xdt, adt.reshape(bh, 1, s), adt.reshape(bh, s, 1), B, C)
    return y, states
