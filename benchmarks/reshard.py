"""Elastic-migration shoot-out: reshard vs full replay, predicted vs measured.

The migration subsystem's claim (repro.atomics.reshard): because ownership
is a pure function of (slot, extent), moving a table to a new mesh costs one
slot exchange — independent of how many RMWs built the table — while the
only alternative, replaying the op history through the sharded tier on the
new mesh, scales with that history.  This benchmark measures both on the
8-fake-device harness (subprocess, XLA_FLAGS before jax init, same pattern
as benchmarks/rmw_sharded.py):

  migrate/device_put   host-roundtrip path, fleet change (2 -> 4 devices)
  migrate/exchange     in-collective all_to_all path, same-fleet layout
                       change ((pod,dev)-sharded -> dev-sharded/pod-replica)
  replay               re-execute the recorded history (4 batches of FAA)
                       through `atomics.execute` on the new mesh

and validates each migrated table bit-for-bit against the replay before
timing.  Predicted costs come from the migration tier of the HardwareSpec
cost model (`cost_migrate_*`, `cost_replay`) so the table doubles as a
predicted-vs-measured check.

The acceptance row (ISSUE 5): migration must beat full replay on every
table of >= 64k slots.  Below that, this host's per-placement dispatch can
rival the handful of collective launches a short replay needs (container
timings are +/-50% noisy); those cells are reported, not gated.

Fake-device caveat (same as rmw_sharded's hierarchical-vs-oneshot): on one
host a "host roundtrip" is a memcpy while a shard_map all_to_all pays
XLA's ms-scale collective dispatch, so the measured exchange path loses to
device_put here even though the cost model — priced for real PCIe vs ICI —
prefers it.  The exchange cell is therefore reported (and verified
bit-identical), never gated on speed.  Emits benchmarks/results/
reshard.json (--fast writes the *_fast.json variant).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from typing import Dict

from benchmarks.common import Csv, refuse_child_on_tpu

RESULT_PATH = os.path.join(os.path.dirname(__file__), "results",
                           "reshard.json")

#: acceptance gate: migration must beat replay from this table size up
GATE_SLOTS = 1 << 16

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from benchmarks.common import time_s
from repro import atomics
from repro.atomics import reshard
from repro.atomics.layout import TableLayout
from repro.core import rmw_engine
from repro.sharding import shard_map_compat

FAST = %(fast)r
devs = jax.devices()
rng = np.random.default_rng(42)
spec = rmw_engine.default_spec()
rows = []

N_BATCHES = 4
N_PER_DEV = 1024 if FAST else 4096
GRID_M = (4096,) if FAST else (4096, 65536, 262144)

def median_time(fn):
    # the shared benchmark clock (telemetry.span under the hood); warmup=1
    # keeps this suite's historical rep budget
    return time_s(fn, warmup=1, name="bench.reshard.rep")

_STEPS = {}

def step_fn(mesh, axis="dev"):
    '''One jitted sharded-FAA step per (mesh, axis) — cached so replay
    timings measure execution, not recompilation (the post-restart step is
    compiled exactly once in a real elastic run too).'''
    key = (id(mesh), axis)
    if key not in _STEPS:
        SPEC = P(tuple(mesh.axis_names))
        def fn(t, i, v):
            h = atomics.AtomicTable(t, axis=axis)
            res = atomics.execute(h, atomics.Faa(i[0], v[0]),
                                  need_fetched=True)
            return res.table.data, res.fetched[None]
        _STEPS[key] = jax.jit(shard_map_compat(
            fn, mesh, (P(axis), SPEC, SPEC), (P(axis), SPEC)))
    return _STEPS[key]

def exec_history(mesh, tbl, history, axis="dev"):
    mapped = step_fn(mesh, axis)
    data = tbl.data
    for i, v in history:
        data, _ = mapped(data, i, v)
    return atomics.AtomicTable(data, axis=axis)

def history_for(mesh, m):
    ndev = int(mesh.devices.size)
    return [(jnp.asarray(rng.integers(0, m, (ndev, N_PER_DEV)), jnp.int32),
             jnp.asarray(rng.integers(-3, 4, (ndev, N_PER_DEV)), jnp.int32))
            for _ in range(N_BATCHES)]

def resplit(history, ndev):
    return [(i.reshape(ndev, -1), v.reshape(ndev, -1)) for i, v in history]

# --- cell 1: fleet change 2 -> 4 (device_put path) ------------------------
mesh2 = Mesh(np.array(devs[:2]), ("dev",))
mesh4 = Mesh(np.array(devs[:4]), ("dev",))
for m in GRID_M:
    hist = history_for(mesh2, m)
    tab0 = jnp.zeros((m,), jnp.int32)
    tbl2 = atomics.AtomicTable(
        jax.device_put(tab0, NamedSharding(mesh2, P("dev"))), axis="dev")
    built = exec_history(mesh2, tbl2, hist)

    src = built.layout()
    dst = TableLayout.from_mesh(mesh4, num_slots=m, dtype=jnp.int32,
                                axis="dev")
    plan = reshard.plan_reshard(src, dst, dst_mesh=mesh4, src_mesh=mesh2)
    migrated = plan.execute(built)

    def replay():
        t = atomics.AtomicTable(
            jax.device_put(tab0, NamedSharding(mesh4, P("dev"))), axis="dev")
        return exec_history(mesh4, t, resplit(hist, 4)).data

    replayed = replay()
    assert np.array_equal(np.asarray(migrated.data), np.asarray(replayed)), \
        f"migrated table != replay at m={m}"

    t_mig = median_time(lambda: plan.execute(built).data)
    t_rep = median_time(replay)
    n_ops = N_BATCHES * N_PER_DEV * 2
    rows.append({
        "cell": "grow_2to4", "path": plan.path, "m": m,
        "history_ops": n_ops,
        "migrate_us": t_mig * 1e6, "replay_us": t_rep * 1e6,
        "speedup_vs_replay": t_rep / t_mig,
        "predicted_migrate_us": plan.predicted_s[plan.path] * 1e6,
        "predicted_replay_us": reshard.cost_replay(
            spec, dst, n_ops, n_batches=N_BATCHES) * 1e6,
    })

# --- cell 2: same-fleet layout change (in-collective exchange path) -------
from repro.sharding import make_mesh
mesh24 = make_mesh((2, 4), ("pod", "dev"))
for m in GRID_M:
    hist = history_for(mesh24, m)
    tab0 = jnp.zeros((m,), jnp.int32)
    built = exec_history(
        mesh24,
        atomics.AtomicTable(
            jax.device_put(tab0, NamedSharding(mesh24, P(("pod", "dev")))),
            axis=("pod", "dev")),
        hist, axis=("pod", "dev"))
    src = built.layout()
    dst = TableLayout.from_mesh(mesh24, num_slots=m, dtype=jnp.int32,
                                axis=("dev",), replica_axes=("pod",))
    plan = reshard.plan_reshard(src, dst, dst_mesh=mesh24, src_mesh=mesh24)
    plan_host = reshard.plan_reshard(src, dst, dst_mesh=mesh24,
                                     src_mesh=mesh24, path="device_put")
    a = plan.execute(built); b = plan_host.execute(built)
    assert np.array_equal(np.asarray(a.data), np.asarray(b.data))
    t_exc = median_time(lambda: plan.execute(built).data)
    t_put = median_time(lambda: plan_host.execute(built).data)
    rows.append({
        "cell": "refleet_8dev", "path": plan.path, "m": m,
        "history_ops": N_BATCHES * N_PER_DEV * 8,
        "migrate_us": t_exc * 1e6, "device_put_us": t_put * 1e6,
        "speedup_vs_device_put": t_put / t_exc,
        "predicted_migrate_us": plan.predicted_s["exchange"] * 1e6,
        "predicted_device_put_us": plan.predicted_s["device_put"] * 1e6,
    })

print("RESULT:" + json.dumps(rows))
"""


def run(csv: Csv, fast: bool = False, out_path: str = RESULT_PATH
        ) -> Dict[str, object]:
    if fast and out_path == RESULT_PATH:
        # never clobber the committed full-grid table with a CI smoke run
        out_path = RESULT_PATH.replace(".json", "_fast.json")
    refuse_child_on_tpu("benchmarks.reshard")
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT % {"fast": fast}], env=env,
        capture_output=True, text=True, timeout=3600,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    if proc.returncode != 0:
        raise RuntimeError(f"reshard bench failed: {proc.stderr[-2000:]}")
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT:")][0]
    rows = json.loads(line[len("RESULT:"):])

    for r in rows:
        alt = ("replay", r["replay_us"]) if "replay_us" in r \
            else ("device_put", r["device_put_us"])
        csv.add(f"reshard.{r['cell']}.m{r['m']}.{r['path']}",
                r["migrate_us"],
                f"{alt[0]}={alt[1]:.0f}us "
                f"pred={r['predicted_migrate_us']:.0f}us")

    # acceptance: migration beats full replay on every >= 64k-slot table
    gated = [r for r in rows
             if r["cell"] == "grow_2to4" and r["m"] >= GATE_SLOTS]
    acceptance = bool(gated) and all(r["speedup_vs_replay"] > 1.0
                                     for r in gated)
    out = {
        "host": {"jax_backend": "cpu", "devices": 8,
                 "meshes": "2dev -> 4dev grow; 2x4 pod*dev refleet"},
        "fast": fast,
        "rows": rows,
        "acceptance_migration_beats_replay_ge_64k_slots": acceptance,
        "gate_slots": GATE_SLOTS,
    }
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(out, f, indent=1)
    csv.add("reshard.acceptance", 0.0,
            f"migration_beats_replay_ge_64k={acceptance} json={out_path}")
    return out
