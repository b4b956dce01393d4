"""RMW engine: every backend agrees bit-exactly with the serialized oracle.

Property-style over collision-heavy index distributions (tiny tables, zipf-y
hot slots, runs of repeats) — the regimes where combining bugs hide.  Also
covers the Pallas kernel's new fetched-value / uniform-CAS outputs and the
cost-model backend selector.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # container image: fall back to the local shim
    from _hypothesis_shim import given, settings, strategies as st

from repro.atomics import arrival_rank
from repro.core import perf_model
from repro.core.rmw import rmw_serialized
from repro.core.rmw_engine import (BACKENDS, execute_backend, rmw_onehot,
                                   select_backend, select_backend_with_cost)
from repro.kernels.rmw.ops import rmw_apply_fetched
from repro.kernels.rmw.ref import rmw_table_fetched_ref

SET = settings(max_examples=25, deadline=None)

RNG = np.random.default_rng(11)


def _collision_heavy(rng, n, m):
    """Mix of hot-slot, uniform, and run-repeated indices."""
    hot = rng.integers(0, max(1, m // 8) or 1, n)
    uni = rng.integers(0, m, n)
    runs = np.repeat(rng.integers(0, m, n // 4 + 1), 4)[:n]
    mix = np.where(rng.random(n) < 0.5, hot, uni)
    mix = np.where(rng.random(n) < 0.25, runs, mix)
    return mix.astype(np.int32)


def batches(max_table=8, max_ops=48, lo=-4, hi=4):
    return st.tuples(
        st.integers(1, max_table),
        st.lists(st.tuples(st.integers(0, max_table - 1),
                           st.integers(lo, hi)), min_size=1,
                 max_size=max_ops))


def _assert_same(a, b, what):
    np.testing.assert_array_equal(np.asarray(a.table), np.asarray(b.table),
                                  err_msg=f"{what}: table")
    np.testing.assert_array_equal(np.asarray(a.fetched), np.asarray(b.fetched),
                                  err_msg=f"{what}: fetched")
    np.testing.assert_array_equal(np.asarray(a.success), np.asarray(b.success),
                                  err_msg=f"{what}: success")


# ---------------------------------------------------------------------------
# onehot backend vs oracle (int dtypes: bit-exact)
# ---------------------------------------------------------------------------

@SET
@given(batches(), st.sampled_from(["faa", "swp", "min", "max"]))
def test_onehot_equals_serialized(batch, op):
    m, ops = batch
    idx = jnp.asarray([i % m for i, _ in ops], jnp.int32)
    vals = jnp.asarray([v for _, v in ops], jnp.int32)
    table = jnp.arange(m, dtype=jnp.int32) - m // 2
    a = rmw_serialized(table, idx, vals, op)
    b = rmw_onehot(table, idx, vals, op, block=16)
    _assert_same(a, b, f"onehot:{op}")
    # table-only mode agrees on the table
    c = rmw_onehot(table, idx, vals, op, block=16, need_fetched=False)
    np.testing.assert_array_equal(np.asarray(a.table), np.asarray(c.table))


@SET
@given(batches(max_table=4, lo=-2, hi=2), st.integers(-2, 2))
def test_onehot_cas_uniform_equals_serialized(batch, expected):
    m, ops = batch
    idx = jnp.asarray([i % m for i, _ in ops], jnp.int32)
    vals = jnp.asarray([v for _, v in ops], jnp.int32)
    table = jnp.asarray([(i % 5) - 2 for i in range(m)], jnp.int32)
    exp_arr = jnp.full((len(ops),), expected, jnp.int32)
    a = rmw_serialized(table, idx, vals, "cas", exp_arr)
    b = rmw_onehot(table, idx, vals, "cas", jnp.int32(expected), block=16)
    _assert_same(a, b, "onehot:cas")
    c = rmw_onehot(table, idx, vals, "cas", jnp.int32(expected), block=16,
                   need_fetched=False)
    np.testing.assert_array_equal(np.asarray(a.table), np.asarray(c.table))


@pytest.mark.parametrize("op", ["faa", "swp", "min", "max"])
@pytest.mark.parametrize("backend", ["sort", "onehot", "serialized"])
def test_backends_agree_collision_heavy(backend, op):
    """Larger batches, blocks straddled, hot slots: all backends identical."""
    m, n = 37, 500
    idx = jnp.asarray(_collision_heavy(RNG, n, m))
    vals = jnp.asarray(RNG.integers(-6, 7, n), jnp.int32)
    table = jnp.asarray(RNG.integers(-5, 6, m), jnp.int32)
    a = rmw_serialized(table, idx, vals, op)
    b = execute_backend(table, idx, vals, op, backend=backend)
    _assert_same(a, b, f"{backend}:{op}")


@pytest.mark.parametrize("backend", ["sort", "onehot"])
def test_backends_cas_collision_heavy(backend):
    m, n = 11, 300
    idx = jnp.asarray(_collision_heavy(RNG, n, m))
    # values drawn from {-1, 0, 1} with expected 0 => live/dead chains mix
    vals = jnp.asarray(RNG.integers(-1, 2, n), jnp.int32)
    table = jnp.asarray(RNG.integers(-1, 2, m), jnp.int32)
    a = rmw_serialized(table, idx, vals, "cas", jnp.zeros((n,), jnp.int32))
    b = execute_backend(table, idx, vals, "cas", jnp.int32(0), backend=backend)
    _assert_same(a, b, f"{backend}:cas")


def test_float_faa_close_across_backends():
    """Float FAA is exact up to reassociation on every backend."""
    m, n = 64, 2048
    idx = jnp.asarray(_collision_heavy(RNG, n, m))
    vals = jnp.asarray(RNG.normal(size=n), jnp.float32)
    table = jnp.asarray(RNG.normal(size=m), jnp.float32)
    ref = rmw_serialized(table, idx, vals, "faa")
    for backend in ("sort", "onehot", "pallas"):
        got = execute_backend(table, idx, vals, "faa", backend=backend)
        np.testing.assert_allclose(np.asarray(got.table),
                                   np.asarray(ref.table),
                                   rtol=1e-4, atol=1e-4, err_msg=backend)
        np.testing.assert_allclose(np.asarray(got.fetched),
                                   np.asarray(ref.fetched),
                                   rtol=1e-4, atol=1e-4, err_msg=backend)


# ---------------------------------------------------------------------------
# Pallas kernel: fetched values + uniform CAS vs the drop-aware oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("op", ["faa", "min", "max", "swp"])
@pytest.mark.parametrize("m,n,tile,block", [
    (128, 256, 128, 128),
    (256, 384, 128, 128),   # multiple tiles AND multiple blocks
    (96, 130, 128, 128),    # padding on both axes
])
def test_pallas_fetched_matches_oracle(op, m, n, tile, block):
    """Integer-valued fp32 => sums exact => bit-exact comparison is valid."""
    table = jnp.asarray(RNG.integers(-8, 9, m), jnp.float32)
    idx = jnp.asarray(_collision_heavy(RNG, n, m + 9))  # some dropped
    vals = jnp.asarray(RNG.integers(-4, 5, n), jnp.float32)
    t, f, s = rmw_apply_fetched(table, idx, vals, op, table_tile=tile,
                                block=block)
    tr, fr, sr = rmw_table_fetched_ref(table, idx, vals, op)
    np.testing.assert_array_equal(np.asarray(t), np.asarray(tr))
    np.testing.assert_array_equal(np.asarray(f), np.asarray(fr))
    np.testing.assert_array_equal(np.asarray(s), np.asarray(sr))


@pytest.mark.parametrize("m,n,tile,block", [
    (128, 256, 128, 128),
    (200, 300, 128, 128),
])
def test_pallas_cas_uniform_matches_oracle(m, n, tile, block):
    # expected = 0 with a table and values full of zeros: dense chain action
    table = jnp.asarray(RNG.integers(-1, 2, m), jnp.float32)
    idx = jnp.asarray(_collision_heavy(RNG, n, m + 5))
    vals = jnp.asarray(RNG.integers(-1, 2, n), jnp.float32)
    t, f, s = rmw_apply_fetched(table, idx, vals, "cas",
                                expected=jnp.float32(0.0),
                                table_tile=tile, block=block)
    tr, fr, sr = rmw_table_fetched_ref(table, idx, vals, "cas",
                                       jnp.float32(0.0))
    np.testing.assert_array_equal(np.asarray(t), np.asarray(tr))
    np.testing.assert_array_equal(np.asarray(f), np.asarray(fr))
    np.testing.assert_array_equal(np.asarray(s), np.asarray(sr))


def test_pallas_fetched_drops_out_of_range():
    table = jnp.zeros((128,), jnp.float32)
    idx = jnp.asarray([0, 0, 127, 128, 10_000], jnp.int32)
    vals = jnp.asarray([1, 2, 3, 4, 5], jnp.float32)
    t, f, s = rmw_apply_fetched(table, idx, vals, "faa", table_tile=128,
                                block=128)
    assert float(t.sum()) == 6.0
    np.testing.assert_array_equal(np.asarray(f), [0.0, 1.0, 0.0, 0.0, 0.0])
    np.testing.assert_array_equal(np.asarray(s), [True, True, True, False,
                                                  False])


# ---------------------------------------------------------------------------
# arrival_rank (sort-free) and the selector
# ---------------------------------------------------------------------------

@SET
@given(st.lists(st.integers(0, 5), min_size=1, max_size=60))
def test_arrival_rank_sortfree_is_faa_fetch(keys):
    k = jnp.asarray(keys, jnp.int32)
    ser = rmw_serialized(jnp.zeros((6,), jnp.int32), k,
                         jnp.ones((len(keys),), jnp.int32), "faa")
    np.testing.assert_array_equal(np.asarray(arrival_rank(k, 6)),
                                  np.asarray(ser.fetched))


def test_arrival_rank_blocked_path_matches_dense():
    # force the blocked (rmw_onehot) path with a big key space
    n, k = 512, 1 << 14
    keys = jnp.asarray(RNG.integers(0, 64, n), jnp.int32)  # still collides
    dense = arrival_rank(keys, 64)
    blocked = arrival_rank(keys, k, block=64)
    np.testing.assert_array_equal(np.asarray(dense), np.asarray(blocked))


def test_selector_prefers_sortfree_on_big_batches():
    """The tentpole regime: FAA batches >= 4k, tables <= 64k slots."""
    for n in (4096, 16384, 65536):
        for m in (256, 4096, 65536):
            assert select_backend("faa", n, m) == "onehot", (n, m)


def test_selector_respects_semantics():
    # general (per-op) expected CAS only has the oracle
    assert select_backend("cas", 10_000, 64,
                          uniform_expected=False) == "serialized"
    # int tables never go to the fp32 pallas kernel
    assert select_backend("swp", 4096, 256, dtype=jnp.int32) != "pallas"


def test_pallas_fetched_path_refused_beyond_its_compile_limit():
    """The fetched kernel compiles one program per table tile: past
    PALLAS_FETCHED_MAX_SLOTS neither auto nor an explicit request runs it,
    while the one-call table-only path still does."""
    from repro.core.rmw_engine import PALLAS_FETCHED_MAX_SLOTS
    big = PALLAS_FETCHED_MAX_SLOTS * 2
    pallas = BACKENDS["pallas"]
    assert pallas.supports("faa", dtype=jnp.float32,
                           m=PALLAS_FETCHED_MAX_SLOTS)
    assert not pallas.supports("faa", dtype=jnp.float32, m=big)
    assert not pallas.supports("cas", dtype=jnp.float32, m=big,
                               need_fetched=False)
    assert pallas.supports("faa", dtype=jnp.float32, m=big,
                           need_fetched=False)
    spec = perf_model.cpu_default_spec()
    sel = select_backend_with_cost("faa", 1024, big, spec,
                                   dtype=jnp.float32)
    assert "pallas" not in sel.costs
    with pytest.raises(ValueError, match="pallas"):
        execute_backend(jnp.zeros((big,), jnp.float32),
                        jnp.zeros((8,), jnp.int32),
                        jnp.ones((8,), jnp.float32), "faa",
                        backend="pallas")


def test_calibrated_spec_raises_for_unknown_tpu_kind(monkeypatch):
    """A TPU whose device_kind has no spec row is an error, not v5e."""
    from types import SimpleNamespace

    from repro.core import rmw_engine
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        jax, "devices",
        lambda *a: [SimpleNamespace(platform="tpu", device_kind="TPU v99")])
    rmw_engine._reset_spec_cache()
    try:
        with pytest.raises(ValueError, match="TPU v99"):
            rmw_engine.calibrated_spec()
        assert perf_model.tpu_spec("TPU v5 lite") is perf_model.TPU_V5E
    finally:
        rmw_engine._reset_spec_cache()


def test_selector_tracks_spec_costs():
    spec = perf_model.cpu_default_spec()
    name = select_backend("faa", 8192, 1024, spec)
    backend = BACKENDS[name]
    others = [b for b in BACKENDS.values()
              if b.supports("faa", dtype=jnp.float32)]
    best = min(o.cost(spec, "faa", 8192, 1024, True) for o in others)
    assert backend.cost(spec, "faa", 8192, 1024, True) == best


def test_execute_validates():
    t = jnp.zeros((4,), jnp.int32)
    i = jnp.zeros((2,), jnp.int32)
    with pytest.raises(ValueError):
        execute_backend(t, i, i, "xor")
    with pytest.raises(ValueError):
        execute_backend(t, i, i, "cas")
    with pytest.raises(ValueError):
        execute_backend(t, i, i, "faa", backend="nope")
    # per-op expected arrays on a uniform-only backend must be rejected,
    # not silently mis-executed
    with pytest.raises(ValueError):
        execute_backend(t, i, i, "cas", jnp.zeros((2,), jnp.int32),
                    backend="onehot")


def test_serialized_backend_takes_uniform_expected():
    """A scalar CAS `expected` on the serialized backend means the same
    expected for every op (it used to fail inside the oracle's scan)."""
    table = jnp.asarray([-1, 3, -1, -1], jnp.int32)
    idx = jnp.asarray([0, 0, 1, 2, 2], jnp.int32)
    vals = jnp.asarray([5, 6, 7, -1, 8], jnp.int32)
    want = rmw_serialized(table, idx, vals, "cas",
                          jnp.full((5,), -1, jnp.int32))
    for backend in ("serialized", "onehot", "sort"):
        got = execute_backend(table, idx, vals, "cas", jnp.int32(-1),
                              backend=backend)
        _assert_same(want, got, backend)


def test_execute_backend_modes_match_oracle():
    """The raw-array engine entry answers identically across backends (the
    facade shim this used to exercise is deleted)."""
    from repro.core.rmw_engine import execute_backend
    table = jnp.zeros((16,), jnp.int32)
    idx = jnp.asarray([1, 1, 2, 15, 1], jnp.int32)
    vals = jnp.asarray([1, 2, 3, 4, 5], jnp.int32)
    ref = rmw_serialized(table, idx, vals, "faa")
    for mode in ("auto", "onehot", "sort", "serialized"):
        got = execute_backend(table, idx, vals, "faa", backend=mode)
        _assert_same(ref, got, mode)
