"""Public attention entry point: pads to block multiples, picks backend."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import kernel as _k
from repro.kernels.flash_attention import ref as _ref

Array = jax.Array


@functools.partial(jax.jit, static_argnames=("causal", "scale", "block_q",
                                             "block_k", "use_kernel"))
def attention(q: Array, k: Array, v: Array, *, causal: bool = True,
              scale: float | None = None,
              block_q: int = _k.DEFAULT_BLOCK_Q,
              block_k: int = _k.DEFAULT_BLOCK_K,
              use_kernel: bool = True) -> Array:
    """Flash attention with padding to block multiples.

    Q and KV are back-padded to block multiples; the kernel masks padded kv
    rows via ``kv_valid`` and keeps the causal diagonal anchored to the real
    lengths via ``kv_offset``; padded query rows are sliced off on exit.
    """
    if not use_kernel:
        return _ref.attention_ref(q, k, v, causal=causal, scale=scale)
    b, hq, sq, d = q.shape
    skv = k.shape[2]
    pad_q = (-sq) % block_q
    pad_k = (-skv) % block_k
    kv_offset = skv - sq
    if pad_q or pad_k:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    out = _k.flash_attention(q, k, v, causal=causal, scale=scale,
                             block_q=block_q, block_k=block_k,
                             kv_valid=skv, kv_offset=kv_offset)
    return out[:, :, :sq, :]
