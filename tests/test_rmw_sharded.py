"""Sharded RMW subsystem: 8-fake-device oracle equivalence + selector props.

The subprocess half (same pattern as tests/test_distributed.py: XLA_FLAGS
must predate jax init) checks the distributed engine against the
single-device serialized oracle under the documented arrival order — the
concatenation of per-device batches by device rank — for FAA/SWP/MIN and
uniform-CAS, fetched and table-only, across every exchange strategy, with
out-of-range drops and the replicated-writer mode.  The in-process half
covers the exchange selector (hierarchical-vs-one-shot crossover), the
hierarchical contention model, the calibrated-spec loader, and the
`repro.core.rmw` namespace fix.
"""

import dataclasses
import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from repro import atomics
from repro.core.rmw import rmw_serialized
from repro.core.bfs import bfs, bfs_sharded, kronecker_graph

rng = np.random.default_rng(7)
from repro.sharding import make_mesh
mesh = make_mesh((2, 4), ("pod", "dev"))
NDEV = 8
SPEC = P(("pod", "dev"))

def shard_map(fn, in_specs, out_specs):
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)

out = {}

def check(op, strategy, need_fetched, dist, axis, replica_axes=(),
          n_per=48, m=64, expected=0):
    n_rep = 2 if replica_axes else 1
    if dist == "hot":
        idx = rng.integers(0, max(2, m // 8), (NDEV, n_per))
    else:
        idx = rng.integers(-2, m + 3, (NDEV, n_per))   # includes OOR
    vals = rng.integers(-5, 6, (NDEV, n_per))
    table0 = rng.integers(-2, 3, m)
    if op == "cas":
        vals = rng.integers(-1, 2, (NDEV, n_per))
        table0 = rng.integers(-1, 2, m)
    idx_j = jnp.asarray(idx, jnp.int32)
    vals_j = jnp.asarray(vals, jnp.int32)
    tab_j = jnp.asarray(table0, jnp.int32)
    tab_spec = SPEC if not replica_axes else P("dev")

    def fn(t, i, v):
        tbl = atomics.AtomicTable(t, axis=axis, replica_axes=replica_axes)
        if op == "cas":
            aop = atomics.Cas(i[0], v[0], expected=jnp.int32(expected))
        else:
            aop = atomics.OP_KINDS[op](i[0], v[0])
        res = atomics.execute(tbl, aop, strategy=strategy,
                              need_fetched=need_fetched)
        return res.table.data, res.fetched[None], res.success[None]

    tabs, fetched, success = shard_map(
        fn, (tab_spec, SPEC, SPEC), (tab_spec, SPEC, SPEC))(
        tab_j, idx_j, vals_j)

    # oracle: concatenated batches in device-rank order, drops to a pad row
    flat_i = idx_j.reshape(-1); flat_v = vals_j.reshape(-1)
    valid = (flat_i >= 0) & (flat_i < m)
    pad_tab = jnp.concatenate([tab_j, jnp.zeros((1,), jnp.int32)])
    ref = rmw_serialized(pad_tab, jnp.where(valid, flat_i, m), flat_v, op,
                         None if op != "cas"
                         else jnp.full((flat_i.shape[0],), expected,
                                       jnp.int32))
    ok = bool(np.array_equal(np.asarray(tabs).reshape(-1)[:m],
                             np.asarray(ref.table)[:m]))
    if need_fetched:
        ok &= bool(np.array_equal(
            np.asarray(fetched).reshape(-1),
            np.asarray(jnp.where(valid, ref.fetched, 0))))
        ok &= bool(np.array_equal(np.asarray(success).reshape(-1),
                                  np.asarray(ref.success & valid)))
    tag = f"{op}/{strategy}/nf={int(need_fetched)}/{dist}/rep={n_rep>1}"
    out[tag] = ok

for op in ("faa", "swp", "cas", "min"):
    for strategy in ("oneshot", "hierarchical", "naive"):
        check(op, strategy, True, "hot", axis=("pod", "dev"))
    check(op, "oneshot", True, "uniform", axis=("pod", "dev"))
    check(op, "oneshot", False, "uniform", axis=("pod", "dev"))
check("faa", "hierarchical", True, "uniform", axis=("pod", "dev"))
check("faa", "dense", False, "hot", axis=("pod", "dev"))
check("faa", "dense", False, "uniform", axis=("pod", "dev"))
# replicated-writer mode: table sharded over dev, replicated over pod;
# arrival order = (pod major, dev minor) = flat device order
for op in ("faa", "swp", "cas"):
    check(op, "oneshot", True, "hot", axis="dev", replica_axes="pod")
check("faa", "dense", False, "hot", axis="dev", replica_axes="pod")

# reverse_ranks: oracle on the batches concatenated in DESCENDING device
# rank (every strategy realizes the same reversed order).  perop=True
# drives the _execute_cas_perop owner-oracle path, which carries its own
# per-level un-flip loop.
def check_reverse(op, strategy, replica_axes=(), n_per=48, m=64,
                  perop=False):
    axis = ("pod", "dev") if not replica_axes else "dev"
    idx = rng.integers(-2, m + 3, (NDEV, n_per))       # includes OOR
    vals = rng.integers(-5, 6, (NDEV, n_per))
    table0 = rng.integers(-2, 3, m)
    if op == "cas":
        vals = rng.integers(-1, 2, (NDEV, n_per))
        table0 = rng.integers(-1, 2, m)
    exps = rng.integers(-1, 2, (NDEV, n_per))          # per-op expected
    idx_j = jnp.asarray(idx, jnp.int32)
    vals_j = jnp.asarray(vals, jnp.int32)
    exps_j = jnp.asarray(exps, jnp.int32)
    tab_j = jnp.asarray(table0, jnp.int32)
    tab_spec = SPEC if not replica_axes else P("dev")

    def fn(t, i, v, e):
        tbl = atomics.AtomicTable(t, axis=axis, replica_axes=replica_axes)
        if op == "cas":
            aop = atomics.Cas(i[0], v[0],
                              expected=e[0] if perop else jnp.int32(0))
        else:
            aop = atomics.OP_KINDS[op](i[0], v[0])
        res = atomics.execute(tbl, aop, strategy=strategy,
                              reverse_ranks=True)
        return res.table.data, res.fetched[None], res.success[None]

    tabs, fetched, success = shard_map(
        fn, (tab_spec, SPEC, SPEC, SPEC), (tab_spec, SPEC, SPEC))(
        tab_j, idx_j, vals_j, exps_j)
    flat_i = idx_j[::-1].reshape(-1)
    flat_v = vals_j[::-1].reshape(-1)
    valid = (flat_i >= 0) & (flat_i < m)
    pad_tab = jnp.concatenate([tab_j, jnp.zeros((1,), jnp.int32)])
    exp_ref = None
    if op == "cas":
        exp_ref = (exps_j[::-1].reshape(-1) if perop
                   else jnp.zeros((flat_i.shape[0],), jnp.int32))
    ref = rmw_serialized(pad_tab, jnp.where(valid, flat_i, m), flat_v, op,
                         exp_ref)
    ok = bool(np.array_equal(np.asarray(tabs).reshape(-1)[:m],
                             np.asarray(ref.table)[:m]))
    ok &= bool(np.array_equal(
        np.asarray(fetched)[::-1].reshape(-1),
        np.asarray(jnp.where(valid, ref.fetched, 0))))
    ok &= bool(np.array_equal(np.asarray(success)[::-1].reshape(-1),
                              np.asarray(ref.success & valid)))
    tag = "cas_perop" if perop else op
    out[f"reverse/{tag}/{strategy}/rep={bool(replica_axes)}"] = ok

for strategy in ("oneshot", "hierarchical", "naive"):
    check_reverse("swp", strategy)
check_reverse("faa", "oneshot")
check_reverse("cas", "oneshot")
check_reverse("swp", "oneshot", replica_axes="pod")
check_reverse("cas", "oneshot", perop=True)            # owner-oracle path
check_reverse("cas", "oneshot", replica_axes="pod", perop=True)

# sharded BFS == single-device BFS (same arrival order => same parents),
# CAS protocol and the SWP+revert protocol (reversed second pass)
src, dst = kronecker_graph(scale=7, edgefactor=8, seed=3)
s = np.concatenate([src, dst]); d = np.concatenate([dst, src])
root = int(s[0])
r_local = bfs(s, d, 128, root=root, op="cas")
r_shard = bfs_sharded(s, d, 128, root=root)
out["bfs_parents_equal"] = bool(np.array_equal(
    np.asarray(r_local.parent), np.asarray(r_shard.parent)))
out["bfs_levels"] = [int(r_local.levels), int(r_shard.levels)]
r_local_swp = bfs(s, d, 128, root=root, op="swp")
r_shard_swp = bfs_sharded(s, d, 128, root=root, op="swp")
out["bfs_swp_parents_equal"] = bool(np.array_equal(
    np.asarray(r_local_swp.parent), np.asarray(r_shard_swp.parent)))
print("RESULT:" + json.dumps(out))
"""


@pytest.fixture(scope="module")
def sharded_result():
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


def test_sharded_matches_serialized_oracle(sharded_result):
    bad = [k for k, v in sharded_result.items()
           if k not in ("bfs_parents_equal", "bfs_levels") and v is not True]
    assert not bad, f"oracle mismatches: {bad}"


def test_sharded_bfs_matches_local(sharded_result):
    assert sharded_result["bfs_parents_equal"] is True
    lvls = sharded_result["bfs_levels"]
    assert lvls[0] == lvls[1]


# ---------------------------------------------------------------------------
# in-process: exchange selector, contention model, loader, namespace
# ---------------------------------------------------------------------------

def _geo_spec():
    """Pods linked by a slow shared WAN pipe — the hierarchy's home turf."""
    from repro.core import perf_model
    from repro.core.placement import Tier
    base = perf_model.cpu_default_spec()
    return dataclasses.replace(
        base,
        tier_bandwidth_Bps={**base.tier_bandwidth_Bps,
                            Tier.DCN_REMOTE_POD: 1e8},
        collective_launch_s=1e-6)


def _axes(outer=2, inner=4):
    from repro.core.placement import Tier
    from repro.core.rmw_sharded import MeshAxis
    return (MeshAxis("pod", outer, Tier.DCN_REMOTE_POD),
            MeshAxis("dev", inner, Tier.ICI_NEIGHBOR))


def test_selector_hierarchical_on_contended_slow_dcn():
    """Contended regime (caps bound by the table): the per-pod tree cuts the
    shared-DCN bytes by the pod fan-in and must win."""
    from repro.core.rmw_sharded import select_exchange
    spec = _geo_spec()
    assert select_exchange("faa", 65536, 1 << 19, _axes(),
                           spec=spec) == "hierarchical"
    assert select_exchange("faa", 65536, 4096, _axes(),
                           spec=spec) == "hierarchical"


def test_selector_oneshot_when_uncombinable_or_flat():
    from repro.core.rmw_sharded import select_exchange
    spec = _geo_spec()
    # small batch against a huge table: nothing to combine, extra level loses
    assert select_exchange("faa", 4096, 1 << 19, _axes(),
                           spec=spec) == "oneshot"
    # a single-axis mesh has no hierarchy to exploit
    assert select_exchange("faa", 65536, 1 << 19, _axes()[1:],
                           spec=spec) == "oneshot"


def test_selector_dense_for_table_only_faa():
    from repro.core.rmw_sharded import select_exchange
    assert select_exchange("faa", 65536, 4096, _axes(), spec=_geo_spec(),
                           need_fetched=False) == "dense"


def test_selector_model_mirrors_benchmark_acceptance():
    """The cost model itself must predict hierarchical < naive on contended
    shapes (the committed benchmark checks the measured version)."""
    from repro.core.rmw_sharded import (cost_exchange_hierarchical,
                                        cost_exchange_naive)
    spec = _geo_spec()
    hier = cost_exchange_hierarchical(spec, "faa", 65536, 4096, _axes())
    naive = cost_exchange_naive(spec, "faa", 65536, 4096, _axes())
    assert hier < naive


def test_selector_rejects_per_op_expected_cas():
    from repro.core.rmw_sharded import select_exchange
    with pytest.raises(ValueError):
        select_exchange("cas", 1024, 4096, _axes(), uniform_expected=False)


def test_contention_hierarchical_beats_flat_tree_over_dcn():
    from repro.core import contention, perf_model
    from repro.core.placement import Tier
    spec = perf_model.TPU_V5E
    flat = contention.contended_bandwidth_combining(
        spec, "faa", 64, remote_tier=Tier.DCN_REMOTE_POD)
    hier = contention.contended_bandwidth_hierarchical(spec, "faa", 4, 16)
    assert hier > flat
    assert contention.hierarchical_crossover_pods(spec, "faa", 16) >= 2


def test_default_spec_loads_calibration(tmp_path, monkeypatch):
    from repro.core import perf_model, rmw_engine
    spec = dataclasses.replace(perf_model.cpu_default_spec(),
                               gather_elem_s=7.5e-9)
    payload = {"jax_backend": "cpu", "spec": perf_model.spec_to_dict(spec)}
    path = tmp_path / "calibrated_spec.json"
    path.write_text(json.dumps(payload))
    monkeypatch.setenv("REPRO_CALIBRATED_SPEC", str(path))
    rmw_engine._reset_spec_cache()
    try:
        assert rmw_engine.default_spec().gather_elem_s == 7.5e-9
    finally:
        rmw_engine._reset_spec_cache()
    # corrupt files must fall back to the priors, never raise
    path.write_text("{not json")
    rmw_engine._reset_spec_cache()
    try:
        assert rmw_engine.default_spec().gather_elem_s \
            == perf_model.cpu_default_spec().gather_elem_s
    finally:
        rmw_engine._reset_spec_cache()


def test_core_rmw_namespace_contract():
    """Post shim removal: `from repro.core import rmw` AND
    `from repro.core import rmw_sharded` both yield plain modules (the PR-2
    function alias for the latter left with the PR-3 shims), and neither
    is callable."""
    import sys
    import types
    import pytest as _pytest
    from repro.core import rmw, rmw_sharded
    assert isinstance(rmw, types.ModuleType)
    assert type(rmw) is types.ModuleType          # not a callable subclass
    assert rmw_sharded is sys.modules["repro.core.rmw_sharded"]
    assert isinstance(rmw_sharded, types.ModuleType)
    with _pytest.raises(TypeError):
        rmw(None)                     # modules are not callable
    with _pytest.raises(TypeError):
        rmw_sharded(None)
