"""Elastic table migration: reshard live AtomicTables across mesh changes.

The acceptance contract of the migration subsystem (ISSUE 5):

* subprocess half (8 fake devices, same pattern as tests/test_rmw_sharded):
  grow (2->4), shrink (4->2), and replica-axis changes through
  `reshard.migrate` / `ReshardPlan.execute` yield tables bit-identical to
  the serialized oracle AND to a from-scratch replay on the new mesh;
  post-migration `atomics.execute` results (fetched/success, per-op-expected
  CAS state, OOR drops) match a never-resharded run; the grow-then-shrink
  round trip (2->4->2) is bit-exact end to end; same-fleet layout changes
  take the in-collective exchange path; checkpointed tables restore under a
  different mesh through `ckpt.restore`; `elastic.reshard_tables` migrates
  live state trees.
* in-process half: TableLayout derivations + serialization, the migration
  cost tier (`select_migration`, migration-vs-replay crossover), plan
  validation errors, `restore_table` fallbacks, local-table checkpoint
  round trips, and the `run_with_recovery` reshard hook.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import atomics
from repro.atomics.layout import TableLayout, local_row, owner_shard
from repro.sharding import make_mesh

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, tempfile
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro import atomics
from repro.atomics import reshard
from repro.atomics.layout import TableLayout
from repro.checkpoint import ckpt
from repro.core.rmw import rmw_serialized
from repro.runtime.elastic import reshard_tables
from repro.sharding import shard_map_compat, use_mesh

rng = np.random.default_rng(11)
devs = jax.devices()
M = 64
out = {}

def mesh_of(k):
    return Mesh(np.array(devs[:k]), ("dev",))

def place(arr, mesh, axis="dev"):
    return jax.device_put(arr, NamedSharding(mesh, P(axis)))

def exec_batch(mesh, tbl, op_name, idx, vals, expected=None,
               replica_axes=(), axis="dev"):
    '''Run one (ndev, n) batch through the sharded tier; returns
    (new AtomicTable, fetched, success) with fetched/success flat in
    device-rank order.'''
    SPEC = P(tuple(mesh.axis_names))
    tab_spec = P(axis)
    args = [tbl.data, idx, vals]
    in_specs = [tab_spec, SPEC, SPEC]
    def fn(t, i, v, *e):
        handle = atomics.AtomicTable(t, axis=axis, replica_axes=replica_axes)
        if op_name == "cas":
            aop = atomics.Cas(i[0], v[0], expected=e[0][0])
        else:
            aop = atomics.OP_KINDS[op_name](i[0], v[0])
        res = atomics.execute(handle, aop)
        return res.table.data, res.fetched[None], res.success[None]
    if op_name == "cas":
        args.append(expected)
        in_specs.append(SPEC)
    tabs, fetched, success = shard_map_compat(
        fn, mesh, tuple(in_specs), (tab_spec, SPEC, SPEC))(*args)
    return (atomics.AtomicTable(tabs, axis=axis, replica_axes=replica_axes),
            np.asarray(fetched).reshape(-1), np.asarray(success).reshape(-1))

def oracle(table, idx, vals, op_name, expected=None):
    '''Serialized oracle with the subsystem's OOR-drop convention.'''
    flat_i = jnp.asarray(idx).reshape(-1)
    flat_v = jnp.asarray(vals).reshape(-1)
    valid = (flat_i >= 0) & (flat_i < M)
    pad = jnp.concatenate([jnp.asarray(table), jnp.zeros((1,), jnp.int32)])
    exp = None if expected is None else jnp.asarray(expected).reshape(-1)
    ref = rmw_serialized(pad, jnp.where(valid, flat_i, M), flat_v, op_name,
                         exp)
    return (np.asarray(ref.table)[:M],
            np.asarray(jnp.where(valid, ref.fetched, 0)),
            np.asarray(ref.success & valid))

def batch(ndev, n=24, dist="mixed"):
    idx = rng.integers(-2, M + 3, (ndev, n))      # includes OOR both sides
    vals = rng.integers(-3, 4, (ndev, n))
    return jnp.asarray(idx, jnp.int32), jnp.asarray(vals, jnp.int32)

# ---------------------------------------------------------------------------
# grow 2 -> 4 and shrink 4 -> 2, every op incl. per-op-expected CAS
# ---------------------------------------------------------------------------

def check_resize(tag, k_from, k_to, op_name):
    mesh_a, mesh_b = mesh_of(k_from), mesh_of(k_to)
    tab0 = jnp.asarray(rng.integers(-1, 2, M), jnp.int32)
    ia, va = batch(k_from)
    ib, vb = batch(k_to)
    ea = eb = None
    if op_name == "cas":
        ea = jnp.asarray(rng.integers(-1, 2, ia.shape), jnp.int32)
        eb = jnp.asarray(rng.integers(-1, 2, ib.shape), jnp.int32)

    tbl = atomics.AtomicTable(place(tab0, mesh_a), axis="dev")
    tbl, _, _ = exec_batch(mesh_a, tbl, op_name, ia, va, ea)
    mig = reshard.migrate(tbl, mesh_b)
    mig2, fb, sb = exec_batch(mesh_b, mig, op_name, ib, vb, eb)

    t1, _, _ = oracle(tab0, ia, va, op_name, ea)
    t2, f2, s2 = oracle(t1, ib, vb, op_name, eb)
    ok = np.array_equal(np.asarray(mig2.data), t2)
    ok &= np.array_equal(fb, f2) and np.array_equal(sb, s2)

    # from-scratch replay of both batches on the NEW mesh reaches the same
    # table — and the migrated route got there without replaying anything
    replay = atomics.AtomicTable(place(tab0, mesh_b), axis="dev")
    ia_r = ia.reshape(k_to, -1); va_r = va.reshape(k_to, -1)
    ea_r = None if ea is None else ea.reshape(k_to, -1)
    replay, _, _ = exec_batch(mesh_b, replay, op_name, ia_r, va_r, ea_r)
    ok &= np.array_equal(np.asarray(replay.data), np.asarray(mig.data))
    out[tag] = bool(ok)

for op_name in ("faa", "swp", "min", "cas"):
    check_resize(f"grow/{op_name}", 2, 4, op_name)
check_resize("shrink/faa", 4, 2, "faa")
check_resize("shrink/max", 4, 2, "max")
check_resize("shrink/cas", 4, 2, "cas")

# ---------------------------------------------------------------------------
# grow-then-shrink round trip (2 -> 4 -> 2): bit-identical to never-resharded
# ---------------------------------------------------------------------------

def check_roundtrip(op_name):
    mesh2, mesh4 = mesh_of(2), mesh_of(4)
    tab0 = jnp.asarray(rng.integers(-1, 2, M), jnp.int32)
    sa_i, sa_v = batch(2)
    sb_i, sb_v = batch(4)           # stream B: executed on the grown mesh
    sc_i, sc_v = batch(2)
    ea = eb = ec = None
    if op_name == "cas":
        ea = jnp.asarray(rng.integers(-1, 2, sa_i.shape), jnp.int32)
        eb = jnp.asarray(rng.integers(-1, 2, sb_i.shape), jnp.int32)
        ec = jnp.asarray(rng.integers(-1, 2, sc_i.shape), jnp.int32)

    # migrated timeline: 2 -> 4 -> 2
    tbl = atomics.AtomicTable(place(tab0, mesh2), axis="dev")
    tbl, _, _ = exec_batch(mesh2, tbl, op_name, sa_i, sa_v, ea)
    tbl = reshard.migrate(tbl, mesh4)
    tbl, _, _ = exec_batch(mesh4, tbl, op_name, sb_i, sb_v, eb)
    tbl = reshard.migrate(tbl, mesh2)
    tbl, fc, sc = exec_batch(mesh2, tbl, op_name, sc_i, sc_v, ec)

    # never-resharded timeline on mesh2: same three GLOBAL op streams (the
    # arrival-order contract maps any device split of a stream to the same
    # serialized order, so stream B re-splits 4 -> 2 losslessly)
    ref = atomics.AtomicTable(place(tab0, mesh2), axis="dev")
    ref, _, _ = exec_batch(mesh2, ref, op_name, sa_i, sa_v, ea)
    ref, _, _ = exec_batch(mesh2, ref, op_name, sb_i.reshape(2, -1),
                           sb_v.reshape(2, -1),
                           None if eb is None else eb.reshape(2, -1))
    ref, fr, sr = exec_batch(mesh2, ref, op_name, sc_i, sc_v, ec)

    ok = np.array_equal(np.asarray(tbl.data), np.asarray(ref.data))
    ok &= np.array_equal(fc, fr) and np.array_equal(sc, sr)
    t1, _, _ = oracle(tab0, sa_i, sa_v, op_name, ea)
    t2, _, _ = oracle(t1, sb_i, sb_v, op_name, eb)
    t3, f3, s3 = oracle(t2, sc_i, sc_v, op_name, ec)
    ok &= np.array_equal(np.asarray(tbl.data), t3)
    ok &= np.array_equal(fc, f3) and np.array_equal(sc, s3)
    out[f"roundtrip/{op_name}"] = bool(ok)

for op_name in ("faa", "swp", "min", "max", "cas"):
    check_roundtrip(op_name)

# ---------------------------------------------------------------------------
# same-fleet layout change rides the in-collective exchange path
# ---------------------------------------------------------------------------

from repro.sharding import make_mesh
mesh24 = make_mesh((2, 4), ("pod", "dev"))
tab0 = jnp.asarray(rng.integers(-1, 2, M), jnp.int32)
tblC = atomics.AtomicTable(
    jax.device_put(tab0, NamedSharding(mesh24, P(("pod", "dev")))),
    axis=("pod", "dev"))
src_lay = tblC.layout()
dst_lay = TableLayout.from_mesh(mesh24, num_slots=M, dtype=jnp.int32,
                                axis=("dev",), replica_axes=("pod",))
plan = reshard.plan_reshard(src_lay, dst_lay, dst_mesh=mesh24,
                            src_mesh=mesh24)
out["exchange/path_selected"] = plan.path == "exchange"
out["exchange/model_orders_paths"] = (plan.predicted_s["exchange"]
                                      < plan.predicted_s["device_put"])
tblR = plan.execute(tblC)
out["exchange/bits"] = bool(np.array_equal(np.asarray(tblR.data),
                                           np.asarray(tab0)))
# the re-derived replica contract actually executes (pod-major arrival)
SPEC = P(("pod", "dev"))
idx = jnp.asarray(rng.integers(0, M, (8, 16)), jnp.int32)
vals = jnp.asarray(rng.integers(-3, 4, (8, 16)), jnp.int32)
def fn_rep(t, i, v):
    h = atomics.AtomicTable(t, axis="dev", replica_axes="pod")
    res = atomics.execute(h, atomics.Faa(i[0], v[0]))
    return res.table.data, res.fetched[None]
tabs, fetched = shard_map_compat(
    fn_rep, mesh24, (P("dev"), SPEC, SPEC), (P("dev"), SPEC))(
    tblR.data, idx, vals)
t_ref, f_ref, _ = oracle(tab0, idx, vals, "faa")
out["exchange/replica_execute"] = bool(
    np.array_equal(np.asarray(tabs).reshape(-1)[:M], t_ref)
    and np.array_equal(np.asarray(fetched).reshape(-1), f_ref))
# exchange and host-roundtrip agree bit for bit
tblR2 = reshard.plan_reshard(src_lay, dst_lay, dst_mesh=mesh24,
                             src_mesh=mesh24,
                             path="device_put").execute(tblC)
out["exchange/agrees_with_device_put"] = bool(
    np.array_equal(np.asarray(tblR.data), np.asarray(tblR2.data)))

# ---------------------------------------------------------------------------
# checkpointed tables restore under a different mesh (layout metadata)
# ---------------------------------------------------------------------------

mesh_a = make_mesh((2, 4), ("pod", "model"))
mesh_b = make_mesh((4, 2), ("pod", "model"))
from repro.sharding import DEFAULT_RULES
with use_mesh(mesh_a, dict(DEFAULT_RULES)):
    tbl = atomics.make_table(M, jnp.int32, fill=0)
tbl = tbl.with_data(place(jnp.asarray(rng.integers(-9, 9, M), jnp.int32),
                          mesh_a, "model"))
d = tempfile.mkdtemp()
ckpt.save(d, 3, {"w": jnp.arange(8.0), "counters": tbl})
man = json.load(open(os.path.join(d, "step-00000003", "manifest.json")))
(meta,) = man["atomic_tables"].values()   # exactly one table in the tree
out["ckpt/meta_layout"] = (meta["axis"] == ["model"]
                           and meta["mesh_axes"] == [["pod", 2],
                                                     ["model", 4]])
like = {"w": jnp.zeros((8,)),
        "counters": atomics.AtomicTable(jnp.zeros((M,), jnp.int32),
                                        axis="model")}
with use_mesh(mesh_b, dict(DEFAULT_RULES)):
    restored, _ = ckpt.restore(d, 3, like)
rt = restored["counters"]
out["ckpt/restored_bits"] = bool(np.array_equal(np.asarray(rt.data),
                                                np.asarray(tbl.data)))
out["ckpt/restored_axis"] = rt.axis == ("model",) or rt.axis == "model"
out["ckpt/restored_on_new_mesh"] = (
    rt.data.sharding.mesh.shape["pod"] == 4)

# ---------------------------------------------------------------------------
# elastic.reshard_tables migrates live state trees
# ---------------------------------------------------------------------------

mesh2, mesh4 = mesh_of(2), mesh_of(4)
live = {"step": jnp.int32(7),
        "tbl": atomics.AtomicTable(place(tab0, mesh2), axis="dev")}
moved = reshard_tables(live, mesh4)
out["elastic/tables_moved"] = bool(
    int(moved["step"]) == 7
    and moved["tbl"].data.sharding.mesh.shape["dev"] == 4
    and np.array_equal(np.asarray(moved["tbl"].data), np.asarray(tab0)))

# non-divisible new extents degrade to a LOCAL handle (make_table's
# divisibility convention) instead of crashing the recovery loop
mesh3 = Mesh(np.array(devs[:3]), ("dev",))
loc = reshard.migrate(
    atomics.AtomicTable(place(jnp.arange(M, dtype=jnp.int32), mesh2),
                        axis="dev"),
    mesh3)                                  # 64 slots over 3 shards
out["elastic/non_divisible_falls_back_local"] = bool(
    loc.axis is None
    and np.array_equal(np.asarray(loc.data), np.arange(M)))

print("RESULT:" + json.dumps(out))
"""


@pytest.fixture(scope="module")
def reshard_result():
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


def test_grow_shrink_matches_oracle_and_replay(reshard_result):
    bad = [k for k, v in reshard_result.items()
           if (k.startswith("grow/") or k.startswith("shrink/"))
           and v is not True]
    assert not bad, f"mismatches: {bad}"


def test_grow_then_shrink_roundtrip_bit_identical(reshard_result):
    bad = [k for k, v in reshard_result.items()
           if k.startswith("roundtrip/") and v is not True]
    assert not bad, f"mismatches: {bad}"


def test_same_fleet_change_uses_exchange_path(reshard_result):
    bad = [k for k, v in reshard_result.items()
           if k.startswith("exchange/") and v is not True]
    assert not bad, f"mismatches: {bad}"


def test_checkpoint_and_elastic_integration(reshard_result):
    bad = [k for k, v in reshard_result.items()
           if (k.startswith("ckpt/") or k.startswith("elastic/"))
           and v is not True]
    assert not bad, f"mismatches: {bad}"


# ---------------------------------------------------------------------------
# in-process: layout derivations + serialization
# ---------------------------------------------------------------------------

def _lay(axis=("pod", "dev"), rep=(), m=64):
    return TableLayout(num_slots=m, dtype="int32", axis=axis,
                       replica_axes=rep,
                       mesh_axes=(("pod", 2), ("dev", 4)))


def test_layout_owner_major_derivations():
    lay = _lay()
    assert lay.n_shards == 8 and lay.m_local == 8 and lay.n_replicas == 1
    assert lay.rows_of_shard(3) == (24, 32)
    assert [lay.shard_of_device(i) for i in range(8)] == list(range(8))
    # replica layout: shard over dev, replicate over pod
    rl = _lay(axis=("dev",), rep=("pod",))
    assert rl.n_shards == 4 and rl.n_replicas == 2 and rl.m_local == 16
    assert [rl.shard_of_device(i) for i in range(8)] == [0, 1, 2, 3] * 2
    assert [rl.replica_rank_of_device(i) for i in range(8)] == [0] * 4 + [1] * 4
    # arrival order: lexicographic over replica_axes + axis (pod major)
    assert [rl.arrival_rank_of_device(i) for i in range(8)] == list(range(8))
    np.testing.assert_array_equal(rl.arrival_order(), np.arange(8))


def test_layout_jnp_helpers_match_python():
    lay = _lay()
    g = jnp.asarray([0, 7, 8, 63, 64, 70], jnp.int32)  # incl. OOR-remapped
    own = owner_shard(g, lay.m_local, lay.n_shards)
    np.testing.assert_array_equal(np.asarray(own), [0, 0, 1, 7, 7, 7])
    rows = local_row(g, own, lay.m_local, lay.num_slots)
    np.testing.assert_array_equal(np.asarray(rows), [0, 7, 0, 7, 8, 8])


def test_layout_serialization_roundtrip_and_errors():
    lay = _lay(axis=("dev",), rep=("pod",))
    assert TableLayout.from_dict(lay.to_dict()) == lay
    with pytest.raises(ValueError, match="divide"):
        TableLayout(num_slots=13, dtype="int32", axis=("dev",),
                    mesh_axes=(("dev", 4),)).m_local
    with pytest.raises(ValueError, match="not on mesh"):
        TableLayout.from_mesh(make_mesh((1,), ("x",)), num_slots=8,
                              dtype=jnp.int32, axis="nope")


def test_table_handle_layout_derivation():
    tbl = atomics.AtomicTable(jnp.zeros((16,), jnp.int32))
    lay = tbl.layout()
    assert not lay.is_sharded and lay.num_slots == 16
    sharded = atomics.AtomicTable(jnp.zeros((16,), jnp.int32), axis="dev")
    with pytest.raises(ValueError, match="mesh"):
        sharded.layout()   # no mesh derivable from a plain local array


# ---------------------------------------------------------------------------
# in-process: the migration cost tier
# ---------------------------------------------------------------------------

def test_select_migration_prefers_exchange_when_feasible():
    from repro.atomics.reshard import (cost_migrate_device_put,
                                       cost_migrate_exchange,
                                       select_migration)
    from repro.core import perf_model
    spec = perf_model.cpu_default_spec()
    src, dst = _lay(), _lay(axis=("dev",), rep=("pod",))
    assert select_migration(src, dst, exchange_feasible=True,
                           spec=spec) == "exchange"
    assert select_migration(src, dst, exchange_feasible=False,
                           spec=spec) == "device_put"
    assert cost_migrate_exchange(spec, src, dst) \
        < cost_migrate_device_put(spec, src, dst)


def test_migration_model_beats_replay_at_64k_slots():
    """The model-level mirror of the benchmark acceptance: moving a >=64k
    table once is cheaper than replaying even a modest op history."""
    from repro.atomics.reshard import cost_migrate_device_put, cost_replay
    from repro.core import perf_model
    spec = perf_model.cpu_default_spec()
    n_batches, n_per_dev, n_dev = 4, 4096, 4   # the benchmark's history
    for m in (1 << 16, 1 << 18):
        lay = TableLayout(num_slots=m, dtype="int32", axis=("dev",),
                          mesh_axes=(("dev", 4),))
        mig = cost_migrate_device_put(spec, lay, lay)
        rep = cost_replay(spec, lay,
                          n_ops_total=n_batches * n_per_dev * n_dev,
                          n_batches=n_batches)
        assert mig < rep * 0.5, (m, mig, rep)  # clear win, not a tie


def test_plan_reshard_validation():
    from repro.atomics.reshard import plan_reshard
    src, dst = _lay(), _lay(m=128)
    with pytest.raises(ValueError, match="slot-count"):
        plan_reshard(src, dst, dst_mesh=None)
    with pytest.raises(ValueError, match="unknown path"):
        plan_reshard(src, _lay(axis=("dev",)), dst_mesh=None, path="teleport")
    with pytest.raises(ValueError, match="same device set"):
        plan_reshard(src, _lay(axis=("dev",)), dst_mesh=None, live=False,
                     path="exchange")


def test_reverse_ranks_rejected_on_local_tier():
    t = jnp.zeros((8,), jnp.int32)
    i = jnp.asarray([1, 2], jnp.int32)
    with pytest.raises(ValueError, match="reverse the batch"):
        atomics.execute(t, atomics.Faa(i, i), reverse_ranks=True)


# ---------------------------------------------------------------------------
# in-process: restore_table + local checkpoint round trip + recovery hook
# ---------------------------------------------------------------------------

def test_restore_table_meshless_falls_back_local():
    from repro.atomics.reshard import restore_table
    host = np.arange(8, dtype=np.int32)
    like = atomics.AtomicTable(jnp.zeros((8,), jnp.int32), axis="model")
    tbl = restore_table(host, like=like)
    assert tbl.axis is None
    np.testing.assert_array_equal(np.asarray(tbl.data), host)
    # meta-only spelling (no like handle in the restore tree)
    tbl2 = restore_table(host, meta={"axis": ["model"]})
    assert tbl2.axis is None
    np.testing.assert_array_equal(np.asarray(tbl2.data), host)


def test_checkpoint_roundtrips_local_table(tmp_path):
    from repro.checkpoint import ckpt
    tbl = atomics.AtomicTable(jnp.arange(6, dtype=jnp.int32))
    ckpt.save(str(tmp_path), 1, {"t": tbl, "x": jnp.ones((3,))})
    like = {"t": atomics.AtomicTable(jnp.zeros((6,), jnp.int32)),
            "x": jnp.zeros((3,))}
    restored, _ = ckpt.restore(str(tmp_path), 1, like)
    assert isinstance(restored["t"], atomics.AtomicTable)
    np.testing.assert_array_equal(np.asarray(restored["t"].data),
                                  np.arange(6))


def test_checkpoint_table_restored_as_array_when_like_holds_array(tmp_path):
    """A leaf the writer stored as an AtomicTable but the caller's `like`
    holds as a plain array restores on the plain path — and sharding_fn is
    consulted for exactly the non-table leaves, keeping positional
    sharding iterators (elastic.reshard_restore) aligned."""
    from repro.checkpoint import ckpt
    tbl = atomics.AtomicTable(jnp.arange(6, dtype=jnp.int32))
    ckpt.save(str(tmp_path), 1, {"t": tbl, "x": jnp.ones((3,))})
    like = {"t": jnp.zeros((6,), jnp.int32), "x": jnp.zeros((3,))}
    consulted = []
    restored, _ = ckpt.restore(
        str(tmp_path), 1, like,
        sharding_fn=lambda key, ref: consulted.append(key))
    assert not isinstance(restored["t"], atomics.AtomicTable)
    np.testing.assert_array_equal(np.asarray(restored["t"]), np.arange(6))
    assert len(consulted) == 2      # every leaf, since none was a table


def test_run_with_recovery_invokes_reshard_hook():
    from repro.runtime.fault_tolerance import FaultConfig, run_with_recovery
    store = {2: 2}
    calls = []

    def reshard_fn(state):
        calls.append(state)
        return state

    crashes = {4: 1}

    def injector(step):
        if crashes.get(step):
            crashes[step] -= 1
            raise RuntimeError("chip lost")

    res = run_with_recovery(
        lambda s, x: x + 1, 0, 6,
        FaultConfig(max_failures=2, checkpoint_every=2),
        lambda step, s: store.__setitem__(step, s),
        lambda: (max(store), store[max(store)]) if store else None,
        failure_injector=injector, reshard_fn=reshard_fn)
    assert res.steps_done == 6 and res.failures == 1
    # hook ran on the initial resume AND on the post-failure restore
    assert len(calls) == 2


def test_run_with_recovery_reshards_scratch_restart_too():
    """No checkpoint to restore -> restart from init_state still crosses
    the mesh change, so the reshard hook must adopt it as well."""
    from repro.runtime.fault_tolerance import FaultConfig, run_with_recovery
    adopted = []
    crashes = {1: 1}

    def injector(step):
        if crashes.get(step):
            crashes[step] -= 1
            raise RuntimeError("chip lost")

    res = run_with_recovery(
        lambda s, x: x + 1, 0, 3,
        FaultConfig(max_failures=2, checkpoint_every=100),
        lambda step, s: None, lambda: None,
        failure_injector=injector,
        reshard_fn=lambda s: (adopted.append(s), s)[1])
    assert res.steps_done == 3
    assert adopted == [0]           # the scratch restart was adopted
