"""Pallas TPU kernel: block-wise causal/full attention with GQA.

Standard streaming-softmax (FlashAttention) schedule adapted to the TPU
memory hierarchy: one (block_q x d) query tile stays VMEM-resident while
(block_k x d) key/value tiles stream HBM->VMEM along the inner ("arbitrary")
grid axis; running max / normalizer / accumulator live in VMEM scratch.
Matmul dims are kept multiples of the 128-lane MXU width by ops.py padding.

GQA is handled in the BlockSpec index maps: query head h reads KV head
h // (Hq // Hkv) — no repeated KV materialization.

Causal blocks strictly above the diagonal are skipped via pl.when (the
block-level analogue of not issuing the read-for-ownership at all).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128

_NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
               scale: float, causal: bool, block_q: int, block_k: int,
               num_k_blocks: int, kv_offset: int, kv_valid: int):
    iq = pl.program_id(1)
    jk = pl.program_id(2)

    @pl.when(jk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # skip kv tiles entirely past the valid region, and (causal) tiles
    # strictly above the diagonal of this query tile
    needed = jk * block_k < kv_valid
    if causal:
        needed &= jk * block_k <= iq * block_q + (block_q - 1) + kv_offset

    @pl.when(needed)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale          # (bq, d)
        k = k_ref[0].astype(jnp.float32)                  # (bk, d)
        v = v_ref[0].astype(jnp.float32)                  # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        kpos = jk * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = kpos < kv_valid
        if causal:
            qpos = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0) + kv_offset
            mask &= kpos <= qpos
        s = jnp.where(mask, s, _NEG_INF)
        m_prev = m_ref[...]                               # (bq, 1)
        l_prev = l_ref[...]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_cur)                            # (bq, bk)
        alpha = jnp.exp(m_prev - m_cur)                   # (bq, 1)
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_cur
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(jk == num_k_blocks - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "scale", "block_q", "block_k", "kv_valid", "kv_offset",
    "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, scale: float | None = None,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    kv_valid: int | None = None,
                    kv_offset: int | None = None,
                    interpret: bool | None = None) -> jax.Array:
    """q: (B, Hq, Sq, D), k/v: (B, Hkv, Skv, D); Sq % block_q == 0,
    Skv % block_k == 0 (ops.py pads).  ``kv_valid`` masks trailing padded kv
    rows; ``kv_offset`` is the causal diagonal shift (real_skv - real_sq).
    Returns (B, Hq, Sq, D).  ``interpret=None`` compiles on TPU and
    interprets elsewhere."""
    b, hq, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    assert hq % hkv == 0, (hq, hkv)
    group = hq // hkv
    if scale is None:
        scale = d ** -0.5
    assert sq % block_q == 0 and skv % block_k == 0, (sq, skv)
    nq, nk = sq // block_q, skv // block_k
    if kv_valid is None:
        kv_valid = skv
    if kv_offset is None:
        kv_offset = skv - sq  # causal alignment when kv longer (cached decode)

    qf = q.reshape(b * hq, sq, d)
    kf = k.reshape(b * hkv, skv, d)
    vf = v.reshape(b * hkv, skv, d)

    def kv_row(bh, i, j):
        del i
        batch = bh // hq
        head = bh % hq
        return batch * hkv + head // group, j, 0

    kernel = functools.partial(
        _fa_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, num_k_blocks=nk, kv_offset=kv_offset,
        kv_valid=kv_valid)

    out = pl.pallas_call(
        kernel,
        grid=(b * hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, block_k, d), kv_row),
            pl.BlockSpec((1, block_k, d), kv_row),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max
            pltpu.VMEM((block_q, 1), jnp.float32),   # running normalizer
            pltpu.VMEM((block_q, d), jnp.float32),   # output accumulator
        ],
        interpret=resolve_interpret(interpret),
    )(qf, kf, vf)
    return out.reshape(b, hq, sq, d)
