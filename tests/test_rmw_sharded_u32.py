"""uint32 slot ids on the sharded tier: tables past 2^31 - 1 slots.

A child process with four fake CPU devices (a 2x2 mesh, as a v5e host)
runs every exchange on a 4096-slot table with uint32 ids, some out of range
and some at 2^31 and above, against `rmw_serialized` on the batches in
device-rank order, and the same ids wrapped to int32; and it lowers the
step of a 2^32-slot table for those four devices without allocating it.
In this process: the owner-major arithmetic at 2^32 and every path that
refuses ids it cannot hold.
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
from repro.atomics.layout import (INT32_SLOTS, TableLayout, local_row,
                                  owner_shard)

OPS = ("faa", "swp", "min", "max", "cas")
STRATEGIES = ("oneshot", "hierarchical", "naive", "auto")
ORACLE_CASES = [f"{op}/{s}" for op in OPS for s in STRATEGIES] + [
    "faa/dense/table_only", "faa/auto/table_only"]
WIDE_STRATEGIES = ("auto", "oneshot", "hierarchical", "naive")

SCRIPT = r"""
import os, sys, json, re
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro import atomics
from repro.core.rmw import rmw_serialized
from repro.sharding import make_mesh, shard_map_compat

mesh = make_mesh((2, 2), ("pod", "dev"))
SPEC = P(("pod", "dev"))
rng = np.random.default_rng(11)
M, N = 4096, 96
out = {}


def step(op, strategy, need_fetched):
    def body(t, i, v):
        tbl = atomics.AtomicTable(t, axis=("pod", "dev"))
        aop = (atomics.Cas(i, v, expected=jnp.int32(0)) if op == "cas"
               else atomics.OP_KINDS[op](i, v))
        r = atomics.execute(tbl, aop, strategy=strategy,
                            need_fetched=need_fetched)
        return r.table.data, r.fetched, r.success
    return jax.jit(shard_map_compat(body, mesh, (SPEC,) * 3, (SPEC,) * 3))


def oracle(op, strategy, need_fetched):
    ids = np.concatenate([
        rng.integers(0, M, (4, N - 24)),
        rng.integers(0, 8, (4, 8)),                   # hot slots
        rng.integers(M, 1 << 31, (4, 8)),             # out of range
        rng.integers(1 << 31, 1 << 32, (4, 8))], 1)   # past int32
    ids = rng.permuted(ids, axis=1).astype(np.uint32)
    lo, hi = (-1, 2) if op == "cas" else (-5, 6)
    vals = rng.integers(lo, hi, (4, N)).astype(np.int32)
    tab = rng.integers(lo, hi, M).astype(np.int32)
    flat = ids.reshape(-1).astype(np.int64)
    valid = flat < M
    ref = rmw_serialized(
        jnp.asarray(np.r_[tab, 0].astype(np.int32)),
        jnp.asarray(np.where(valid, flat, M).astype(np.int32)),
        jnp.asarray(vals.reshape(-1)), op,
        None if op != "cas" else jnp.zeros(flat.shape, jnp.int32))
    ok = True
    for dtype in (np.uint32, np.int32):               # int32: ids wrapped
        t, f, s = step(op, strategy, need_fetched)(
            jnp.asarray(tab), jnp.asarray(ids.reshape(-1).astype(dtype)),
            jnp.asarray(vals.reshape(-1)))
        ok &= np.array_equal(np.asarray(t), np.asarray(ref.table)[:M])
        if need_fetched:
            ok &= np.array_equal(np.asarray(f),
                                 np.where(valid, np.asarray(ref.fetched), 0))
            ok &= np.array_equal(np.asarray(s),
                                 np.asarray(ref.success) & valid)
    return bool(ok)


for op in ("faa", "swp", "min", "max", "cas"):
    for strategy in ("oneshot", "hierarchical", "naive", "auto"):
        out[f"{op}/{strategy}"] = oracle(op, strategy, True)
for strategy in ("dense", "auto"):
    out[f"faa/{strategy}/table_only"] = oracle("faa", strategy, False)

# the step of a 2^32-slot table, lowered from shapes alone
sh = NamedSharding(mesh, SPEC)
wide = (jax.ShapeDtypeStruct((1 << 32,), jnp.int32, sharding=sh),
        jax.ShapeDtypeStruct((1 << 20,), jnp.uint32, sharding=sh),
        jax.ShapeDtypeStruct((1 << 20,), jnp.int32, sharding=sh))


def wide_hlo(op, strategy, need_fetched=True):
    text = step(op, strategy, need_fetched).lower(*wide).as_text()
    # attributes (replica groups, dimensions) are i64; arrays must not be
    text = re.sub(r"dense<[^>]*>\s*:\s*tensor<[^>]*>", "", text)
    return text


lowered = {}
for strategy in ("auto", "oneshot", "hierarchical", "naive"):
    text = wide_hlo("faa", strategy)
    lowered[strategy] = {
        "wide_ints": sorted(set(re.findall(r"[iu]64>", text))),
        "all_to_all": "all_to_all" in text,
        "reduce_scatter": "reduce_scatter" in text}
text = wide_hlo("faa", "auto", need_fetched=False)
lowered["auto/table_only"] = {
    "wide_ints": sorted(set(re.findall(r"[iu]64>", text))),
    "all_to_all": "all_to_all" in text,
    "reduce_scatter": "reduce_scatter" in text}
out["lowered"] = lowered


def refused(fn):
    try:
        fn()
    except ValueError as e:
        return str(e)
    return None


def lower_with(**kw):
    def body(t, i, v):
        tbl = atomics.AtomicTable(t, axis=("pod", "dev"))
        r = atomics.execute(tbl, atomics.Faa(i, v), **kw)
        return r.table.data, r.fetched
    return lambda: jax.jit(shard_map_compat(
        body, mesh, (SPEC,) * 3, (SPEC, SPEC))).lower(*wide)


def lower_perop_cas():
    def body(t, i, v):
        tbl = atomics.AtomicTable(t, axis=("pod", "dev"))
        r = atomics.execute(tbl, atomics.Cas(i, v, expected=v))
        return r.table.data, r.fetched
    return jax.jit(shard_map_compat(
        body, mesh, (SPEC,) * 3, (SPEC, SPEC))).lower(*wide)


def lower_int32_ids():
    ids = jax.ShapeDtypeStruct((1 << 20,), jnp.int32, sharding=sh)
    return step("faa", "oneshot", True).lower(wide[0], ids, wide[2])


out["refused"] = {
    "collect_stats": refused(lower_with(collect_stats=True)),
    "dense": refused(lower_with(strategy="dense", need_fetched=False)),
    "perop_cas": refused(lower_perop_cas),
    "int32_ids": refused(lower_int32_ids)}
print("RESULT:" + json.dumps(out))
"""


@pytest.fixture(scope="module")
def wide_result():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=os.path.join(root, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600,
                          cwd=root)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT:")][0]
    return json.loads(line[len("RESULT:"):])


@pytest.mark.parametrize("case", ORACLE_CASES)
def test_uint32_ids_match_serialized_oracle(wide_result, case):
    """Bit-identical to `rmw_serialized`, under uint32 ids and the same ids
    wrapped to int32 (ids past 2^31 are then negative: out of range too)."""
    assert wide_result[case] is True


@pytest.mark.parametrize("strategy", WIDE_STRATEGIES)
def test_wide_step_lowers_without_64_bit_arrays(wide_result, strategy):
    """The FAA step of a 2^32-slot table lowers for a 2x2 mesh: no Python
    int past int32 reaches an int32 array, no array is 64-bit, and the ops
    travel by all-to-all, not by the dense exchange's reduce-scatter."""
    got = wide_result["lowered"][strategy]
    assert got == {"wide_ints": [], "all_to_all": True,
                   "reduce_scatter": False}


def test_wide_table_only_step_does_not_pick_dense(wide_result):
    got = wide_result["lowered"]["auto/table_only"]
    assert got == {"wide_ints": [], "all_to_all": True,
                   "reduce_scatter": False}


@pytest.mark.parametrize("path,limit", [
    ("collect_stats", str(INT32_SLOTS)), ("perop_cas", str(INT32_SLOTS)),
    ("int32_ids", "uint32"), ("dense", "17179869188 bytes")])
def test_wide_table_refuses_what_int32_cannot_hold(wide_result, path, limit):
    msg = wide_result["refused"][path]
    assert msg is not None and limit in msg


@pytest.mark.parametrize("gidx,owner,row", [
    (2**32 - 1, 3, 2**30 - 1), (2**31, 2, 0), (2**31 - 1, 1, 2**30 - 1),
    (2**30, 1, 0), (0, 0, 0)])
def test_owner_and_row_at_2_32(gidx, owner, row):
    g = jnp.asarray([gidx], jnp.uint32)
    own = owner_shard(g, 2**30, 4)
    assert own.dtype == jnp.int32 and int(own[0]) == owner
    got = local_row(g, own, 2**30, 2**32)
    assert got.dtype == jnp.int32 and int(got[0]) == row


def test_owner_and_row_out_of_range_below_2_32():
    """A uint32 table bound under 2^32: ids at and past it go to the last
    shard's scratch row."""
    g = jnp.asarray([3 * 2**29, 3 * 2**29 - 1, 2**32 - 1], jnp.uint32)
    own = owner_shard(g, 2**29, 3)
    np.testing.assert_array_equal(np.asarray(own), [2, 2, 2])
    np.testing.assert_array_equal(
        np.asarray(local_row(g, own, 2**29, 3 * 2**29)),
        [2**29, 2**29 - 1, 2**29])


def test_auto_never_picks_dense_past_a_chip():
    from repro.core.placement import Tier
    from repro.core.rmw_sharded import MeshAxis, select_exchange_with_cost
    axes = (MeshAxis("pod", 2, Tier.DCN_REMOTE_POD),
            MeshAxis("dev", 2, Tier.ICI_NEIGHBOR))
    wide = select_exchange_with_cost("faa", 1 << 18, 1 << 32, axes,
                                     need_fetched=False)
    assert wide.choice != "dense" and wide.costs["dense"] == float("inf")
    # up to INT32_SLOTS slots dense is priced as before
    assert select_exchange_with_cost("faa", 1 << 18, 4096, axes,
                                     need_fetched=False).costs["dense"] \
        < float("inf")


def test_local_tier_refuses_uint32_ids_past_int32():
    table = jax.ShapeDtypeStruct((INT32_SLOTS + 1,), jnp.int8)
    ids = jax.ShapeDtypeStruct((8,), jnp.uint32)
    vals = jax.ShapeDtypeStruct((8,), jnp.int8)
    with pytest.raises(ValueError, match=str(INT32_SLOTS)):
        jax.eval_shape(lambda t, i, v: atomics.execute(
            t, atomics.Faa(i, v)).table.data, table, ids, vals)


def test_execute_until_refuses_a_table_past_int32():
    wide = atomics.AtomicTable(jnp.zeros((1,), jnp.int32)).with_data(
        jax.ShapeDtypeStruct((1 << 32,), jnp.int32))
    with pytest.raises(ValueError, match=str(INT32_SLOTS)):
        atomics.execute_until(wide, lambda s, o: atomics.Cas(
            jnp.zeros((4,), jnp.uint32), jnp.ones((4,), jnp.int32),
            expected=jnp.int32(0)))


def test_reshard_exchange_refuses_a_table_past_int32():
    from repro.atomics.reshard import plan_reshard
    lay = TableLayout(num_slots=1 << 32, dtype="int32", axis=("pod", "dev"),
                      mesh_axes=(("pod", 2), ("dev", 2)))
    with pytest.raises(ValueError, match=str(INT32_SLOTS)):
        plan_reshard(lay, lay, dst_mesh=None, path="exchange")
    # auto prices the exchange as infeasible and takes device_put
    assert plan_reshard(lay, lay, dst_mesh=None).path == "device_put"

