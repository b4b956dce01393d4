"""Mesh-wide sharded atomics demo: the unified `repro.atomics` API, live.

    PYTHONPATH=src python examples/sharded_atomics.py [--n-per-device 8192]

Spins up 8 fake host devices as a (2 pods x 4 devices) mesh, hammers one
hot table shard with FAA batches from every device (the paper's §5.4
contention workload), and runs the same typed op batch through every
exchange strategy — verifying they agree bit-for-bit with the single-device
serialized oracle under the documented arrival order, and timing naive
per-op exchange vs one-shot vs hierarchical combining.  Then demonstrates
the two capabilities unique to the unified front-end: **per-op-expected
CAS across shards** (the owner-side oracle pass) and the **dynamic
contention hint** for `select_exchange`.  Ends with a sharded-frontier BFS
whose parents match the single-device run.
"""

import argparse
import os
import time

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax                                                    # noqa: E402
import jax.numpy as jnp                                       # noqa: E402
import numpy as np                                            # noqa: E402
from jax.sharding import PartitionSpec as P                   # noqa: E402

from repro import atomics                                     # noqa: E402
from repro.core.bfs import bfs, bfs_sharded, kronecker_graph  # noqa: E402
from repro.core.rmw import rmw_serialized                     # noqa: E402
from repro.core.rmw_sharded import MeshAxis, select_exchange  # noqa: E402
from repro.core.placement import Tier                         # noqa: E402
from repro.sharding import (DEFAULT_RULES, make_mesh,  # noqa: E402
                            shard_map_compat, use_mesh)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n-per-device", type=int, default=8192)
    ap.add_argument("--table", type=int, default=4096)
    args = ap.parse_args()

    ndev = jax.device_count()
    mesh = make_mesh((2, ndev // 2), ("pod", "model"))
    n, m = args.n_per_device, args.table
    rng = np.random.default_rng(0)
    # 95% of every device's ops hit 8 slots of shard 0 — the hot line
    hot = rng.integers(0, 8, (ndev, n))
    uni = rng.integers(0, m, (ndev, n))
    idx = np.where(rng.random((ndev, n)) < 0.95, hot, uni).astype(np.int32)
    vals = rng.integers(-5, 6, (ndev, n)).astype(np.int32)

    spec = P(("pod", "model"))
    axis = ("pod", "model")

    def run(strategy):
        def fn(t, i, v):
            tbl = atomics.AtomicTable(t, axis=axis)
            res = atomics.execute(tbl, atomics.Faa(i[0], v[0]),
                                  strategy=strategy)
            return res.table.data, res.fetched[None]
        return jax.jit(shard_map_compat(fn, mesh, (spec, spec, spec),
                                        (spec, spec)))

    with use_mesh(mesh, dict(DEFAULT_RULES)):
        # the RMW table is a first-class typed object: make_table places it
        # via the "rmw_table" logical-axis rule and records the mesh axes
        table = atomics.make_table(m, jnp.int32)
        print(f"make_table under the mesh -> {table}")
    idx_j, vals_j = jnp.asarray(idx), jnp.asarray(vals)
    table0 = jnp.zeros((m,), jnp.int32)

    ref = rmw_serialized(table0, idx_j.reshape(-1),
                         vals_j.reshape(-1), "faa")
    axes = (MeshAxis("pod", 2, Tier.DCN_REMOTE_POD),
            MeshAxis("model", ndev // 2, Tier.ICI_NEIGHBOR))
    pick = select_exchange("faa", n, m, axes)
    print(f"{ndev} devices (2 pods x {ndev // 2}), {n} ops/device, "
          f"table {m} ({m // ndev}/shard), hot shard 0 — "
          f"cost model picks: {pick}\n")
    for strategy in ("naive", "oneshot", "hierarchical"):
        fn = run(strategy)
        tab, fetched = jax.block_until_ready(fn(table0, idx_j, vals_j))
        t0 = time.perf_counter()
        for _ in range(3):
            jax.block_until_ready(fn(table0, idx_j, vals_j))
        dt = (time.perf_counter() - t0) / 3
        exact = (np.array_equal(np.asarray(tab), np.asarray(ref.table)) and
                 np.array_equal(np.asarray(fetched).reshape(-1),
                                np.asarray(ref.fetched)))
        print(f"{strategy:13s}: {dt * 1e3:8.2f} ms/batch   "
              f"bit-identical-to-oracle={exact}")

    # --- per-op-expected CAS across shards (the owner-side oracle pass) ---
    n_cas = min(n, 2048)
    cidx = jnp.asarray(rng.integers(0, m, (ndev, n_cas)), jnp.int32)
    cvals = jnp.asarray(rng.integers(-1, 2, (ndev, n_cas)), jnp.int32)
    cexp = jnp.asarray(rng.integers(-1, 2, (ndev, n_cas)), jnp.int32)

    def cas_fn(t, i, v, e):
        tbl = atomics.AtomicTable(t, axis=axis)
        res = atomics.execute(tbl, atomics.Cas(i[0], v[0], expected=e[0]))
        return res.table.data, res.fetched[None], res.success[None]

    tab, fetched, success = jax.jit(shard_map_compat(
        cas_fn, mesh, (spec, spec, spec, spec), (spec, spec, spec)))(
        table0, cidx, cvals, cexp)
    cref = rmw_serialized(table0, cidx.reshape(-1), cvals.reshape(-1),
                          "cas", cexp.reshape(-1))
    exact = (np.array_equal(np.asarray(tab), np.asarray(cref.table)) and
             np.array_equal(np.asarray(fetched).reshape(-1),
                            np.asarray(cref.fetched)) and
             np.array_equal(np.asarray(success).reshape(-1),
                            np.asarray(cref.success)))
    print(f"\nper-op-expected CAS across shards ({n_cas}/device): "
          f"bit-identical-to-oracle={exact}")

    # --- the dynamic contention hint sharpens the exchange crossover ------
    # Demonstrated on the cost model at multi-pod scale (slow shared DCN
    # uplink, real collective-launch costs): this single-host container's
    # fake "DCN" is a memcpy, so the one-shot-vs-hierarchical crossover
    # only exists in the model — exactly where select_exchange reads it.
    import dataclasses
    from repro.core import perf_model
    base = perf_model.cpu_default_spec()
    geo = dataclasses.replace(
        base,
        tier_bandwidth_Bps={**base.tier_bandwidth_Bps,
                            Tier.DCN_REMOTE_POD: 1e8},
        collective_launch_s=1e-4)
    stat = select_exchange("faa", 65536, 1 << 19, axes, spec=geo)
    hint = select_exchange("faa", 65536, 1 << 19, axes, spec=geo,
                           distinct_slots=16)
    print(f"contention hint (slow-DCN spec, 64k ops/device, 512k table): "
          f"static caps pick {stat!r}; distinct_slots=16 (skewed batch) "
          f"picks {hint!r}")

    print("\nsharded-frontier BFS (parent table = the contended line):")
    src, dst = kronecker_graph(scale=10, edgefactor=8, seed=1)
    s = np.concatenate([src, dst])
    d = np.concatenate([dst, src])
    root = int(s[0])
    r_local = bfs(s, d, 1 << 10, root=root, op="cas")
    r_shard = bfs_sharded(s, d, 1 << 10, root=root, axis="dev")
    same = np.array_equal(np.asarray(r_local.parent),
                          np.asarray(r_shard.parent))
    print(f"levels={r_shard.levels} edges={r_shard.edges_traversed} "
          f"parents match single-device: {same}")


if __name__ == "__main__":
    main()
