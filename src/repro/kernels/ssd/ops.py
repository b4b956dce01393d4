"""Public SSD entry points: chunked scan (train/prefill) + decode step.

Composition (ops layer):
  1. kernel: per-chunk intra output + chunk states       (quadratic, MXU)
  2. jnp:    log-depth associative scan over chunk states (cross-chunk)
  3. jnp:    y += C·exp(l)·H_prev  inter-chunk term       (two einsums)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.ssd import kernel as _k
from repro.kernels.ssd import ref as _ref

Array = jax.Array


@functools.partial(jax.jit, static_argnames=("chunk", "return_final_state"))
def ssd_chunked_jnp(x: Array, dt: Array, A: Array, B: Array, C: Array, *,
                    chunk: int = _k.DEFAULT_CHUNK,
                    return_final_state: bool = False):
    """Differentiable chunked SSD in pure jnp (same math as the kernel).

    Keeps the head axis explicit throughout so TP sharding of heads over the
    `model` mesh axis propagates (no (B*H) merges that would force gathers).
    Used on CPU, and as the backward pass of the kernel path.
    """
    b, s, h, p = x.shape
    n = B.shape[-1]
    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0), (0, 0)))
    sp = s + pad
    nc = sp // chunk

    def r(t):  # (B, S, H, ...) -> (B, NC, Q, H, ...)
        return t.reshape(b, nc, chunk, *t.shape[2:])

    xdt = r((x * dt[..., None]).astype(jnp.float32))          # (b,c,q,h,p)
    adt = r((dt * A[None, None, :]).astype(jnp.float32))      # (b,c,q,h)
    Br, Cr = r(B.astype(jnp.float32)), r(C.astype(jnp.float32))

    l = jnp.cumsum(adt, axis=2)                               # (b,c,q,h)
    lt = l[:, :, :, None, :]                                  # (b,c,q,1,h)
    ls = l[:, :, None, :, :]                                  # (b,c,1,k,h)
    tpos = jnp.arange(chunk)
    mask = tpos[None, :] <= tpos[:, None]                     # (q,k) s<=t
    # mask before exp: above the diagonal l_t - l_s > 0 overflows past a
    # chunk of ~128 steps, and where(mask, inf, 0)'s gradient is 0*inf = NaN
    m = jnp.exp(jnp.where(mask[None, None, :, :, None], lt - ls, -jnp.inf))
    scores = jnp.einsum("bcqhn,bckhn->bcqkh", Cr, Br)
    y_intra = jnp.einsum("bcqkh,bckhp->bcqhp", scores * m, xdt)

    decay_end = jnp.exp(l[:, :, -1:, :] - l)                  # (b,c,q,h)
    states = jnp.einsum("bckhn,bckhp->bchnp", Br, xdt * decay_end[..., None])
    ltot = l[:, :, -1, :]                                     # (b,c,h)
    decay = jnp.exp(ltot)[..., None, None]                    # (b,c,h,1,1)

    def comb(a2, c2):
        d1, s1 = a2
        d2, s2 = c2
        return d1 * d2, d2 * s1 + s2

    _, h_incl = jax.lax.associative_scan(comb, (decay, states), axis=1)
    h_prev = jnp.concatenate(
        [jnp.zeros_like(h_incl[:, :1]), h_incl[:, :-1]], axis=1)
    y_inter = jnp.einsum("bcqhn,bchnp->bcqhp", Cr * jnp.exp(l)[..., None],
                         h_prev)
    y = (y_intra + y_inter).reshape(b, sp, h, p)[:, :s].astype(x.dtype)
    if return_final_state:
        return y, h_incl[:, -1]                               # (b,h,n,p)
    return y


@functools.partial(jax.jit, static_argnames=("chunk", "use_kernel",
                                             "return_final_state"))
def ssd(x: Array, dt: Array, A: Array, B: Array, C: Array, *,
        chunk: int = _k.DEFAULT_CHUNK, use_kernel: bool | None = None,
        return_final_state: bool = False):
    """Chunked SSD.  x (B,S,H,P), dt (B,S,H), A (H,), B/C (B,S,H,N).
    With return_final_state, also returns h_final (B,H,N,P) for decode.
    use_kernel: None = the Pallas kernel on TPU, jnp elsewhere.  The
    gradient never goes through ``pallas_call``: the kernel path's backward
    pass is `ssd_chunked_jnp`'s."""
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    if not use_kernel:
        return ssd_chunked_jnp(x, dt, A, B, C, chunk=chunk,
                               return_final_state=return_final_state)
    return _ssd_kernel_path(x, dt, A, B, C, chunk, return_final_state)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _ssd_kernel_path(x, dt, A, B, C, chunk, return_final_state):
    b, s, h, p = x.shape
    n = B.shape[-1]
    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))   # dt=0 => identity step
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0), (0, 0)))
    sp = s + pad
    nc = sp // chunk

    # (B,S,H,*) -> (B*H, S, *)
    def flat(t):
        return t.transpose(0, 2, 1, *range(3, t.ndim)).reshape(
            b * h, sp, *t.shape[3:])

    xdt = flat(x * dt[..., None]).astype(jnp.float32)
    adt = (dt * A[None, None, :]).transpose(0, 2, 1).reshape(b * h, sp) \
        .astype(jnp.float32)
    Bf, Cf = flat(B).astype(jnp.float32), flat(C).astype(jnp.float32)

    y_intra, states = _k.ssd_chunk(xdt, adt, Bf, Cf, chunk=chunk)

    # cross-chunk recurrence: H_c = exp(Ltot_c) * H_{c-1} + S_c
    l = jnp.cumsum(adt.reshape(b * h, nc, chunk), axis=-1)     # (BH,NC,Q)
    ltot = l[..., -1]                                          # (BH,NC)
    decay = jnp.exp(ltot)[..., None, None]                     # (BH,NC,1,1)

    def comb(a, c):
        d1, s1 = a
        d2, s2 = c
        return d1 * d2, d2 * s1 + s2

    _, h_incl = jax.lax.associative_scan(comb, (decay, states), axis=1)
    h_prev = jnp.concatenate(
        [jnp.zeros_like(h_incl[:, :1]), h_incl[:, :-1]], axis=1)  # entering st.

    # inter-chunk: y_t += (C_t * exp(l_t)) @ H_prev(chunk(t))
    cdecay = Cf.reshape(b * h, nc, chunk, n) * jnp.exp(l)[..., None]
    y_inter = jnp.einsum("zcqn,zcnp->zcqp", cdecay, h_prev)
    y = y_intra.reshape(b * h, nc, chunk, p) + y_inter
    y = y.reshape(b * h, sp, p).reshape(b, h, sp, p).transpose(0, 2, 1, 3)
    y = y[:, :s].astype(x.dtype)
    if return_final_state:
        # NOTE: with padding, padded steps have dt=0 => exp(0)*h + 0 = h, so
        # the final inclusive state equals the state after the real prefix.
        h_final = h_incl[:, -1].reshape(b, h, n, p)
        return y, h_final
    return y


def _ssd_kernel_path_fwd(x, dt, A, B, C, chunk, return_final_state):
    out = _ssd_kernel_path(x, dt, A, B, C, chunk, return_final_state)
    return out, (x, dt, A, B, C)


def _ssd_kernel_path_bwd(chunk, return_final_state, res, g):
    _, vjp = jax.vjp(functools.partial(
        ssd_chunked_jnp, chunk=chunk,
        return_final_state=return_final_state), *res)
    return vjp(g)


_ssd_kernel_path.defvjp(_ssd_kernel_path_fwd, _ssd_kernel_path_bwd)


def _final_state_ref(x, dt, A, B, C):
    b, s, h, p = x.shape
    n = B.shape[-1]

    def per_bh(xbh, dtbh, a, bbh, cbh):
        def step(hs, inp):
            xt, dtt, bt, _ = inp
            return jnp.exp(a * dtt) * hs + dtt * jnp.outer(bt, xt), None
        h0 = jnp.zeros((n, p), jnp.float32)
        hf, _ = jax.lax.scan(step, h0, (xbh.astype(jnp.float32),
                                        dtbh.astype(jnp.float32),
                                        bbh.astype(jnp.float32),
                                        cbh.astype(jnp.float32)))
        return hf

    f = jax.vmap(jax.vmap(per_bh, in_axes=(1, 1, 0, 1, 1), out_axes=0),
                 in_axes=(0, 0, None, 0, 0), out_axes=0)
    return f(x, dt, A, B, C)


@jax.jit
def ssd_decode_step(hstate: Array, x: Array, dt: Array, A: Array, B: Array,
                    C: Array) -> tuple[Array, Array]:
    """One-token decode: carries hstate (B,H,N,P) — O(1) in context length.

    This is why the SSM archs run the `long_500k` cell (DESIGN.md §5)."""
    return _ref.ssd_decode_ref(hstate, x, dt, A, B, C)
