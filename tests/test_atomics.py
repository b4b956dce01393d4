"""Unified `repro.atomics` front-end: the ONE public RMW surface.

The acceptance contract of the API redesign (ISSUE 3), post shim removal
(ISSUE 5 deleted the PR-3 deprecation shims after their one-release window):

* `atomics.execute` is bit-identical to the serialized oracle for
  FAA/SWP/MIN/MAX, uniform-expected CAS *and* per-op-expected CAS,
  single-device and on an 8-fake-device mesh (subprocess half, same
  pattern as tests/test_rmw_sharded.py).
* the legacy entry points (``rmw_run``/``rmw.rmw``, ``rmw_execute``,
  ``rmw_sharded.rmw_sharded``, both old ``arrival_rank`` spellings) are
  GONE — `test_legacy_shims_are_deleted` pins that they never come back.
* typed constructors validate shapes; `AtomicTable` handles are pytrees
  carrying the mesh contract; `make_table` wires the ``"rmw_table"``
  logical-sharding rule; a sharded table outside shard_map fails with
  guidance; `select_exchange` honours the dynamic contention hint.
"""

import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import atomics
from repro.core.rmw import rmw_serialized
from repro.sharding import make_mesh

RNG = np.random.default_rng(5)

OPS = atomics.OP_KINDS


def _batch(n=300, m=17):
    idx = jnp.asarray(RNG.integers(-2, m + 3, n), jnp.int32)  # incl. OOR
    idx = jnp.clip(idx, 0, m - 1)  # local tier: keep in range
    vals = jnp.asarray(RNG.integers(-6, 7, n), jnp.int32)
    table = jnp.asarray(RNG.integers(-5, 6, m), jnp.int32)
    return table, idx, vals


def _assert_result(res, ref, what, table_only=False):
    np.testing.assert_array_equal(np.asarray(res.table.data),
                                  np.asarray(ref.table),
                                  err_msg=f"{what}: table")
    if not table_only:
        np.testing.assert_array_equal(np.asarray(res.fetched),
                                      np.asarray(ref.fetched),
                                      err_msg=f"{what}: fetched")
        np.testing.assert_array_equal(np.asarray(res.success),
                                      np.asarray(ref.success),
                                      err_msg=f"{what}: success")


# ---------------------------------------------------------------------------
# local tier: bit-identical to the oracle and to the legacy entries
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("op", ["faa", "swp", "min", "max"])
def test_execute_equals_oracle(op):
    table, idx, vals = _batch()
    ref = rmw_serialized(table, idx, vals, op)
    res = atomics.execute(table, OPS[op](idx, vals))
    _assert_result(res, ref, f"atomics:{op}")
    # ... and the raw-array engine entry (the internal tier) agrees
    from repro.core.rmw_engine import execute_backend
    raw = execute_backend(table, idx, vals, op)
    _assert_result(res, raw, f"engine:{op}")


def test_execute_cas_uniform_equals_oracle():
    m, n = 11, 300
    idx = jnp.asarray(RNG.integers(0, m, n), jnp.int32)
    vals = jnp.asarray(RNG.integers(-1, 2, n), jnp.int32)
    table = jnp.asarray(RNG.integers(-1, 2, m), jnp.int32)
    ref = rmw_serialized(table, idx, vals, "cas", jnp.zeros((n,), jnp.int32))
    res = atomics.execute(table, atomics.Cas(idx, vals, expected=0))
    _assert_result(res, ref, "cas-uniform")


def test_execute_cas_perop_equals_oracle():
    """Per-op expected locally: auto-routes to the serialized oracle."""
    m, n = 11, 200
    idx = jnp.asarray(RNG.integers(0, m, n), jnp.int32)
    vals = jnp.asarray(RNG.integers(-1, 2, n), jnp.int32)
    exp = jnp.asarray(RNG.integers(-1, 2, n), jnp.int32)
    table = jnp.asarray(RNG.integers(-1, 2, m), jnp.int32)
    ref = rmw_serialized(table, idx, vals, "cas", exp)
    res = atomics.execute(table, atomics.Cas(idx, vals, expected=exp))
    _assert_result(res, ref, "cas-perop")


def test_execute_table_only_and_backend_override():
    table, idx, vals = _batch()
    ref = rmw_serialized(table, idx, vals, "faa")
    for backend in ("auto", "sort", "onehot", "serialized"):
        res = atomics.execute(table, atomics.Faa(idx, vals),
                              backend=backend, need_fetched=False)
        _assert_result(res, ref, f"table-only:{backend}", table_only=True)


def test_execute_op_sequence_folds_in_order():
    table, idx, vals = _batch()
    ref1 = rmw_serialized(table, idx, vals, "faa")
    ref2 = rmw_serialized(ref1.table, idx, vals, "max")
    res = atomics.execute(table, [atomics.Faa(idx, vals),
                                  atomics.Max(idx, vals)])
    np.testing.assert_array_equal(np.asarray(res.table.data),
                                  np.asarray(ref2.table))
    assert isinstance(res.fetched, tuple) and len(res.fetched) == 2
    np.testing.assert_array_equal(np.asarray(res.fetched[0]),
                                  np.asarray(ref1.fetched))
    np.testing.assert_array_equal(np.asarray(res.fetched[1]),
                                  np.asarray(ref2.fetched))


# ---------------------------------------------------------------------------
# sharded tier in-process (1-device mesh): detection + legacy parity
# ---------------------------------------------------------------------------

def _one_dev_shard_map(fn, mesh, n_in, n_out):
    from jax.sharding import PartitionSpec as P
    from repro.sharding import shard_map_compat
    return shard_map_compat(fn, mesh, (P(),) * n_in, (P(),) * n_out)


def test_execute_sharded_detection_and_parity_one_device():
    mesh = make_mesh((1,), ("x",))
    table, idx, vals = _batch()
    ref = rmw_serialized(table, idx, vals, "faa")

    def fn(t, i, v):
        tbl = atomics.AtomicTable(t, axis="x")
        res = atomics.execute(tbl, atomics.Faa(i, v))
        return res.table.data, res.fetched, res.success

    tab, fetched, success = _one_dev_shard_map(fn, mesh, 3, 3)(
        table, idx, vals)
    np.testing.assert_array_equal(np.asarray(tab), np.asarray(ref.table))
    np.testing.assert_array_equal(np.asarray(fetched),
                                  np.asarray(ref.fetched))


def test_sharded_table_outside_shard_map_raises_with_guidance():
    table, idx, vals = _batch()
    tbl = atomics.AtomicTable(table, axis="model")
    with pytest.raises(ValueError, match="shard_map"):
        atomics.execute(tbl, atomics.Faa(idx, vals))


def test_local_table_rejects_sharded_tier_arguments():
    """Naming an exchange strategy (or hint) against a local table is almost
    always a migration that forgot AtomicTable(axis=...) — error, don't
    silently run the local tier and drop the exchange."""
    table, idx, vals = _batch()
    with pytest.raises(ValueError, match="AtomicTable"):
        atomics.execute(table, atomics.Faa(idx, vals), strategy="oneshot")
    with pytest.raises(ValueError, match="AtomicTable"):
        atomics.execute(table, atomics.Faa(idx, vals), distinct_slots=8)


def test_sharded_perop_cas_rejects_non_oracle_backend():
    """Sharded per-op CAS mirrors the local tier: an explicit non-oracle
    backend override raises instead of being silently ignored."""
    mesh = make_mesh((1,), ("x",))
    m, n = 8, 16
    table = jnp.zeros((m,), jnp.int32)
    idx = jnp.zeros((n,), jnp.int32)
    vals = jnp.ones((n,), jnp.int32)
    exp = jnp.zeros((n,), jnp.int32)

    def fn(t, i, v, e):
        tbl = atomics.AtomicTable(t, axis="x")
        res = atomics.execute(tbl, atomics.Cas(i, v, expected=e),
                              backend="onehot")
        return res.table.data

    with pytest.raises(ValueError, match="serialized oracle"):
        _one_dev_shard_map(fn, mesh, 4, 1)(table, idx, vals, exp)


# ---------------------------------------------------------------------------
# typed constructors + table handle
# ---------------------------------------------------------------------------

def test_op_constructors_validate():
    i2 = jnp.zeros((2,), jnp.int32)
    v3 = jnp.zeros((3,), jnp.int32)
    with pytest.raises(ValueError, match="batch size"):
        atomics.Faa(i2, v3)
    with pytest.raises(ValueError, match="1-D"):
        atomics.Swp(jnp.zeros((2, 2), jnp.int32), jnp.zeros((4,), jnp.int32))
    with pytest.raises(ValueError, match="expected"):
        atomics.Cas(i2, i2, expected=None)
    with pytest.raises(ValueError, match="per-op expected"):
        atomics.Cas(i2, i2, expected=v3)
    assert atomics.Cas(i2, i2, expected=0).uniform_expected
    assert not atomics.Cas(i2, i2, expected=i2).uniform_expected


def test_execute_rejects_untyped_ops():
    table, idx, vals = _batch()
    with pytest.raises(TypeError, match="atomics.Faa"):
        atomics.execute(table, (idx, vals, "faa"))
    with pytest.raises(ValueError, match="empty"):
        atomics.execute(table, [])


def test_atomic_table_is_pytree_through_jit():
    tbl = atomics.AtomicTable(jnp.zeros((8,), jnp.int32), axis="model",
                              replica_axes=("data",))
    out = jax.jit(lambda t: t.with_data(t.data + 1))(tbl)
    assert isinstance(out, atomics.AtomicTable)
    assert out.axis == "model" and out.replica_axes == ("data",)
    assert int(out.data.sum()) == 8
    # ops are pytrees too
    op = atomics.Cas(jnp.zeros((2,), jnp.int32), jnp.ones((2,), jnp.int32),
                     expected=jnp.zeros((2,), jnp.int32))
    leaves = jax.tree_util.tree_leaves(op)
    assert len(leaves) == 3


def test_make_table_without_mesh_is_local():
    tbl = atomics.make_table(16, jnp.float32, fill=2.5)
    assert not tbl.is_sharded and tbl.axis is None
    assert tbl.dtype == jnp.float32 and float(tbl.data[3]) == 2.5


def test_replica_axes_without_axis_rejected():
    """A 'replicated but unsharded' table would silently drop the
    replica-major write contract — both constructors must refuse it."""
    with pytest.raises(ValueError, match="replica_axes requires axis"):
        atomics.AtomicTable(jnp.zeros((8,), jnp.int32),
                            replica_axes=("data",))
    # make_table: no mesh -> the rmw_table rule resolves to nothing
    with pytest.raises(ValueError, match="replica_axes"):
        atomics.make_table(16, jnp.int32, replica_axes=("data",))


# ---------------------------------------------------------------------------
# the PR-3 shims completed their one-release window and are deleted —
# pin the removal so they cannot quietly come back
# ---------------------------------------------------------------------------

def test_legacy_shims_are_deleted():
    import repro.core as core
    from repro.core import rmw_engine, rmw_sharded
    from repro.core import rmw as rmw_mod
    for holder, name in ((rmw_mod, "rmw"), (rmw_mod, "arrival_rank"),
                         (rmw_engine, "rmw_execute"),
                         (rmw_engine, "arrival_rank"),
                         (rmw_sharded, "rmw_sharded"),
                         (core, "rmw_run"), (core, "rmw_execute"),
                         (core, "arrival_rank"), (core, "RmwConfig")):
        assert not hasattr(holder, name), \
            f"{holder.__name__}.{name} shim resurrected"
    # ... and the internal raw-array entries remain
    assert callable(rmw_engine.execute_backend)
    assert callable(rmw_sharded.execute_sharded)


def test_arrival_rank_canonical_spellings_agree():
    keys = jnp.asarray(RNG.integers(0, 5, 64), jnp.int32)
    want = atomics.arrival_rank(keys, 5)          # sort-free
    np.testing.assert_array_equal(np.asarray(atomics.arrival_rank(keys)),
                                  np.asarray(want))  # argsort fallback


# ---------------------------------------------------------------------------
# dynamic contention hint (select_exchange)
# ---------------------------------------------------------------------------

def _hint_spec():
    from repro.core import perf_model
    from repro.core.placement import Tier
    base = perf_model.cpu_default_spec()
    return dataclasses.replace(
        base,
        tier_bandwidth_Bps={**base.tier_bandwidth_Bps,
                            Tier.DCN_REMOTE_POD: 1e8},
        collective_launch_s=1e-4)


def _hint_axes():
    from repro.core.placement import Tier
    from repro.core.rmw_sharded import MeshAxis
    return (MeshAxis("pod", 2, Tier.DCN_REMOTE_POD),
            MeshAxis("dev", 4, Tier.ICI_NEIGHBOR))


def test_contention_hint_shifts_exchange_crossover():
    """Static caps say 'big contended batch -> hierarchical'; an observed
    distinct-slot estimate of a *skewed* batch (few slots -> tiny combined
    payload) flips the pick to one-shot, because the DCN savings no longer
    pay for the extra level's launches.  Wide estimates must not flip."""
    from repro.core.rmw_sharded import select_exchange
    spec, axes = _hint_spec(), _hint_axes()
    assert select_exchange("faa", 65536, 1 << 19, axes,
                           spec=spec) == "hierarchical"
    assert select_exchange("faa", 65536, 1 << 19, axes, spec=spec,
                           distinct_slots=64) == "oneshot"
    assert select_exchange("faa", 65536, 1 << 19, axes, spec=spec,
                           distinct_slots=65536) == "hierarchical"


def test_contention_hint_never_changes_results():
    """The hint reaches only the selector: execution with an absurd hint is
    still bit-identical (1-device mesh exercises the full dispatch path)."""
    mesh = make_mesh((1,), ("x",))
    table, idx, vals = _batch()
    ref = rmw_serialized(table, idx, vals, "faa")

    def fn(t, i, v):
        tbl = atomics.AtomicTable(t, axis="x")
        res = atomics.execute(tbl, atomics.Faa(i, v), distinct_slots=1)
        return res.table.data, res.fetched

    tab, fetched = _one_dev_shard_map(fn, mesh, 3, 2)(table, idx, vals)
    np.testing.assert_array_equal(np.asarray(tab), np.asarray(ref.table))
    np.testing.assert_array_equal(np.asarray(fetched),
                                  np.asarray(ref.fetched))


# ---------------------------------------------------------------------------
# 8-fake-device subprocess: per-op-expected CAS across shards + make_table
# ---------------------------------------------------------------------------

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from repro import atomics
from repro.core.rmw import rmw_serialized
from repro.sharding import DEFAULT_RULES, shard_map_compat, use_mesh

rng = np.random.default_rng(13)
from repro.sharding import make_mesh
mesh = make_mesh((2, 4), ("pod", "dev"))
NDEV = 8
SPEC = P(("pod", "dev"))
out = {}

def check_perop_cas(tag, dist, replica_axes=(), n_per=48, m=64):
    axis = ("pod", "dev") if not replica_axes else "dev"
    if dist == "hot":
        idx = rng.integers(0, max(2, m // 8), (NDEV, n_per))
    else:
        idx = rng.integers(-2, m + 3, (NDEV, n_per))   # includes OOR
    vals = rng.integers(-1, 2, (NDEV, n_per))
    exps = rng.integers(-1, 2, (NDEV, n_per))          # PER-OP expected
    table0 = rng.integers(-1, 2, m)
    idx_j = jnp.asarray(idx, jnp.int32)
    vals_j = jnp.asarray(vals, jnp.int32)
    exps_j = jnp.asarray(exps, jnp.int32)
    tab_j = jnp.asarray(table0, jnp.int32)
    tab_spec = SPEC if not replica_axes else P("dev")

    def fn(t, i, v, e):
        tbl = atomics.AtomicTable(t, axis=axis, replica_axes=replica_axes)
        res = atomics.execute(tbl, atomics.Cas(i[0], v[0], expected=e[0]))
        return res.table.data, res.fetched[None], res.success[None]

    tabs, fetched, success = shard_map_compat(
        fn, mesh, (tab_spec, SPEC, SPEC, SPEC), (tab_spec, SPEC, SPEC))(
        tab_j, idx_j, vals_j, exps_j)

    # oracle: device-rank-ordered concatenation, per-op expected concatenated
    flat_i = idx_j.reshape(-1); flat_v = vals_j.reshape(-1)
    flat_e = exps_j.reshape(-1)
    valid = (flat_i >= 0) & (flat_i < m)
    pad_tab = jnp.concatenate([tab_j, jnp.zeros((1,), jnp.int32)])
    ref = rmw_serialized(pad_tab, jnp.where(valid, flat_i, m), flat_v,
                         "cas", flat_e)
    ok = bool(np.array_equal(np.asarray(tabs).reshape(-1)[:m],
                             np.asarray(ref.table)[:m]))
    ok &= bool(np.array_equal(
        np.asarray(fetched).reshape(-1),
        np.asarray(jnp.where(valid, ref.fetched, 0))))
    ok &= bool(np.array_equal(np.asarray(success).reshape(-1),
                              np.asarray(ref.success & valid)))
    out[tag] = ok

check_perop_cas("perop_cas/hot", "hot")
check_perop_cas("perop_cas/uniform_with_oor", "uniform")
check_perop_cas("perop_cas/hot/replicated", "hot", replica_axes="pod")
check_perop_cas("perop_cas/uniform/replicated", "uniform",
                replica_axes="pod")

# table-only per-op CAS agrees on the table
idx = jnp.asarray(rng.integers(0, 64, (NDEV, 40)), jnp.int32)
vals = jnp.asarray(rng.integers(-1, 2, (NDEV, 40)), jnp.int32)
exps = jnp.asarray(rng.integers(-1, 2, (NDEV, 40)), jnp.int32)
tab0 = jnp.asarray(rng.integers(-1, 2, 64), jnp.int32)
def fn_to(t, i, v, e):
    tbl = atomics.AtomicTable(t, axis=("pod", "dev"))
    res = atomics.execute(tbl, atomics.Cas(i[0], v[0], expected=e[0]),
                          need_fetched=False)
    return res.table.data
tabs = shard_map_compat(fn_to, mesh, (SPEC, SPEC, SPEC, SPEC), SPEC)(
    tab0, idx, vals, exps)
ref = rmw_serialized(tab0, idx.reshape(-1), vals.reshape(-1), "cas",
                     exps.reshape(-1))
out["perop_cas/table_only"] = bool(np.array_equal(
    np.asarray(tabs).reshape(-1), np.asarray(ref.table)))

# make_table wires the "rmw_table" logical rule to the model axis
mesh2 = make_mesh((2, 4), ("pod", "model"))
with use_mesh(mesh2, dict(DEFAULT_RULES)):
    tbl = atomics.make_table(4096, jnp.int32)
out["make_table/axis_is_model"] = tbl.axis == "model"
# sharded 4-ways over model (4 distinct slices), replicated over pod
out["make_table/sharded_over_4"] = (
    len(set(str(s.index) for s in tbl.data.addressable_shards)) == 4)
# non-divisible tables fall back to local (the divisibility-aware rule)
with use_mesh(mesh2, dict(DEFAULT_RULES)):
    tbl_odd = atomics.make_table(13, jnp.int32)
out["make_table/non_divisible_local"] = tbl_odd.axis is None

print("RESULT:" + json.dumps(out))
"""


@pytest.fixture(scope="module")
def atomics_sharded_result():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900,
                          cwd=os.path.dirname(os.path.dirname(
                              os.path.abspath(__file__))))
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines()
            if l.startswith("RESULT:")][0]
    return json.loads(line[len("RESULT:"):])


def test_perop_cas_across_shards_matches_oracle(atomics_sharded_result):
    bad = [k for k, v in atomics_sharded_result.items() if v is not True]
    assert not bad, f"mismatches: {bad}"
