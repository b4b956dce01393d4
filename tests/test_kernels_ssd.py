"""SSD kernel + jnp chunked path vs sequential reference."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.ssd.ops import ssd, ssd_chunked_jnp, ssd_decode_step
from repro.kernels.ssd.ref import ssd_ref

RNG = np.random.default_rng(13)


def _inputs(b, s, h, p, n):
    x = jnp.asarray(RNG.normal(size=(b, s, h, p)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.01, 0.2, size=(b, s, h)), jnp.float32)
    A = jnp.asarray(-RNG.uniform(0.5, 2.0, size=(h,)), jnp.float32)
    B = jnp.asarray(RNG.normal(size=(b, s, h, n)), jnp.float32)
    C = jnp.asarray(RNG.normal(size=(b, s, h, n)), jnp.float32)
    return x, dt, A, B, C


SWEEP = [(2, 64, 3, 16, 8, 16), (1, 100, 2, 8, 4, 32), (1, 32, 1, 4, 4, 32),
         (2, 48, 4, 8, 16, 8)]


@pytest.mark.parametrize("b,s,h,p,n,chunk", SWEEP)
def test_pallas_kernel_matches_ref(b, s, h, p, n, chunk):
    args = _inputs(b, s, h, p, n)
    got = ssd(*args, chunk=chunk, use_kernel=True)
    want = ssd_ref(*args)
    np.testing.assert_allclose(got, want, rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("b,s,h,p,n,chunk", SWEEP)
def test_jnp_chunked_matches_ref(b, s, h, p, n, chunk):
    args = _inputs(b, s, h, p, n)
    got = ssd_chunked_jnp(*args, chunk=chunk)
    want = ssd_ref(*args)
    np.testing.assert_allclose(got, want, rtol=3e-4, atol=3e-4)


def test_final_state_consistency():
    args = _inputs(1, 40, 2, 8, 4)
    y1, h1 = ssd(*args, chunk=8, use_kernel=True, return_final_state=True)
    y2, h2 = ssd_chunked_jnp(*args, chunk=8, return_final_state=True)
    np.testing.assert_allclose(h1, h2, rtol=3e-4, atol=3e-4)
    # continuing with the state matches running the longer sequence
    x, dt, A, B, C = _inputs(1, 41, 2, 8, 4)
    y_full = ssd_ref(x, dt, A, B, C)
    y_pre, h_pre = ssd_chunked_jnp(x[:, :40], dt[:, :40], A, B[:, :40],
                                   C[:, :40], chunk=8,
                                   return_final_state=True)
    h_step, y_last = ssd_decode_step(h_pre, x[:, 40], dt[:, 40], A,
                                     B[:, 40], C[:, 40])
    np.testing.assert_allclose(y_last, y_full[:, -1], rtol=1e-3, atol=1e-4)


def test_grad_through_jnp_path():
    args = _inputs(1, 32, 2, 8, 4)
    g = jax.grad(lambda x: ssd_chunked_jnp(x, *args[1:], chunk=8).sum()
                 )(args[0])
    assert np.isfinite(np.asarray(g)).all()


@pytest.mark.parametrize("use_kernel", [False, True])
def test_grad_finite_when_chunk_decay_overflows(use_kernel):
    """mamba2's init (A = -1, dt = softplus(0) ~ 0.69) over a 256-step
    chunk: exp(l_t - l_s) above the diagonal overflows float32, and the
    masked-out entries must not turn the gradient into NaN."""
    b, s, h, p, n = 1, 256, 2, 8, 8
    x, _, _, B, C = _inputs(b, s, h, p, n)
    dt = jnp.full((b, s, h), float(jax.nn.softplus(0.0)), jnp.float32)
    A = -jnp.ones((h,), jnp.float32)
    g = jax.grad(lambda *a: ssd(*a, chunk=256, use_kernel=use_kernel).sum(),
                 argnums=(0, 1, 2, 3, 4))(x, dt, A, B, C)
    for gi in g:
        assert np.isfinite(np.asarray(gi)).all()


def test_dt_zero_is_identity_step():
    """dt=0 => exp(0)*h + 0: state unchanged (padding correctness)."""
    x, dt, A, B, C = _inputs(1, 16, 2, 8, 4)
    h0 = jnp.asarray(RNG.normal(size=(1, 2, 4, 8)), jnp.float32)
    h1, y = ssd_decode_step(h0, x[:, 0], jnp.zeros_like(dt[:, 0]), A,
                            B[:, 0], C[:, 0])
    np.testing.assert_allclose(h1, h0, rtol=1e-6)


@pytest.mark.parametrize("return_final_state", [False, True])
def test_kernel_path_grad_matches_jnp(return_final_state):
    """The kernel path (interpreter here) differentiates through
    `ssd_chunked_jnp`'s backward pass: same gradients, no pallas_call vjp."""
    args = _inputs(1, 40, 2, 8, 4)

    def loss(use_kernel):
        def f(x, dt, A, B, C):
            out = ssd(x, dt, A, B, C, chunk=16, use_kernel=use_kernel,
                      return_final_state=return_final_state)
            if return_final_state:
                y, h = out
                return (y ** 2).sum() + (h ** 2).sum()
            return (out ** 2).sum()
        return f

    got = jax.grad(loss(True), argnums=(0, 1, 2, 3, 4))(*args)
    want = jax.grad(loss(False), argnums=(0, 1, 2, 3, 4))(*args)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5)
