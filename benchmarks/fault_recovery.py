"""Recovery-latency and bounded-retry benchmark (ISSUE 6 acceptance rows).

Three experiments, emitted to benchmarks/results/fault_recovery.json
(--fast writes the *_fast.json variant):

  recovery/p<rate>     wall-clock + recovery overhead of a checkpointed
                       run under a seeded chaos plan firing step faults at
                       the given probability, against a tmpdir store; each
                       cell re-validates the final state bit-equal to the
                       fault-free run (the determinism contract — recovery
                       must cost time, never correctness).
  retry/<policy>/n<n>  `atomics.execute_until` convergence on a fully-
                       contended CAS batch (n ops -> one slot, the textbook
                       CAS-increment loop): rounds, total attempts, wall
                       time.  Gate: the immediate and exponential policies
                       resolve in <= n rounds (serialized equivalence says
                       one winner per round); shrink trades extra rounds
                       for fewer attempts and is gated on attempts only.
  retry/sharded/n16    the same contended batch through the sharded tier —
                       an 8-fake-device (2,4) mesh in a subprocess (fast
                       mode: a 1-device mesh in-process) — gated on the
                       same <= n bound, closing the "local AND sharded"
                       acceptance clause.

The recovery grid uses `FaultConfig(backoff_base_s=0)` so the measured
overhead is restore+replay work, not configured sleeps (backoff pacing is
benchmarked by its pure function, not by actually sleeping)."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import tempfile
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import Csv, refuse_child_on_tpu
from repro import telemetry

RESULT_PATH = os.path.join(os.path.dirname(__file__), "results",
                           "fault_recovery.json")

_SHARDED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
import numpy as np
from repro import atomics
from repro.atomics import Cas, execute_until

from repro.sharding import make_mesh
mesh = make_mesh((2, 4), ("pod", "dev"))
P = jax.sharding.PartitionSpec
n = 16

def make_table():
    data = jax.device_put(
        jnp.zeros((32,), jnp.int32),
        jax.sharding.NamedSharding(mesh, P(("pod", "dev"))))
    return atomics.AtomicTable(data, axis=("pod", "dev"))

def make_ops(slots, observed):
    if slots is None:
        return Cas(jnp.zeros((n,), jnp.int32), jnp.ones((n,), jnp.int32),
                   expected=jnp.zeros((n,), jnp.int32))
    return Cas(jnp.asarray(slots), jnp.asarray(observed) + 1,
               expected=jnp.asarray(observed))

from repro import telemetry
res = execute_until(make_table(), make_ops, max_rounds=n)  # warm compile
with telemetry.span("bench.retry.sharded", n=n) as sp:
    res = execute_until(make_table(), make_ops, max_rounds=n)
dt = sp.wall_s
out = {"n": n, "n_rounds": int(res.n_rounds),
       "pending": int(res.pending.size),
       "attempts": int(res.rounds.sum()),
       "final": int(np.asarray(res.table.data)[0]), "seconds": dt}
print("RESULT:" + json.dumps(out))
"""


def _contended_make_ops(n):
    def make_ops(slots, observed):
        from repro.atomics import Cas
        if slots is None:
            return Cas(jnp.zeros((n,), jnp.int32), jnp.ones((n,), jnp.int32),
                       expected=jnp.zeros((n,), jnp.int32))
        return Cas(jnp.asarray(slots), jnp.asarray(observed) + 1,
                   expected=jnp.asarray(observed))
    return make_ops


def _recovery_grid(csv: Csv, fast: bool) -> list:
    from repro import atomics
    from repro.checkpoint import ckpt
    from repro.runtime.chaos import FaultPlan, SiteSpec
    from repro.runtime.fault_tolerance import FaultConfig, run_with_recovery

    n_steps = 20 if fast else 40
    m = 32

    def step_fn(step, state):
        table, acc = state
        idx = jnp.asarray((np.arange(8) * (step + 3)) % m, jnp.int32)
        res = atomics.execute(table, atomics.Faa(
            idx, jnp.asarray(np.arange(8) + step, jnp.int32)))
        return res.table, acc + jnp.sum(res.fetched)

    def run_once(root, prob):
        ckpt_dir = os.path.join(root, f"p{prob}")
        like = {"table": atomics.AtomicTable(jnp.zeros((m,), jnp.int32)),
                "acc": jnp.int32(0)}

        def restore_fn():
            got = ckpt.restore_latest_valid(ckpt_dir, like)
            if got is None:
                return None
            s, tree, _ = got
            return s, (tree["table"], tree["acc"])

        plan = (FaultPlan.null() if prob == 0.0 else
                FaultPlan(7, {"step": SiteSpec(prob=prob, count=6)}))
        cfg = FaultConfig(max_failures=20, checkpoint_every=5,
                          backoff_base_s=0.0)
        with telemetry.span("bench.recovery", prob=prob) as sp:
            res = run_with_recovery(
                step_fn,
                (atomics.AtomicTable(jnp.zeros((m,), jnp.int32)),
                 jnp.int32(0)),
                n_steps, cfg,
                lambda s, st: ckpt.save(ckpt_dir, s,
                                        {"table": st[0], "acc": st[1]}),
                restore_fn, chaos=plan, sleep_fn=lambda d: None)
        dt = sp.wall_s
        final = restore_fn()
        return {"prob": prob, "seconds": dt, "failures": res.failures,
                "restored_from": res.restored_from,
                "final_step": final[0],
                "table": np.asarray(final[1][0].data).tolist(),
                "acc": int(final[1][1])}

    rows = []
    root = tempfile.mkdtemp(prefix="fault_recovery_")
    try:
        run_once(os.path.join(root, "warm"), 0.0)   # absorb jit compiles
        base = run_once(root, 0.0)
        for prob in (0.0, 0.05, 0.2):
            cell = base if prob == 0.0 else run_once(root, prob)
            bit_equal = (cell["table"] == base["table"]
                         and cell["acc"] == base["acc"]
                         and cell["final_step"] == n_steps)
            assert bit_equal, (
                f"recovery at fault rate {prob} diverged from fault-free")
            row = {"name": f"recovery/p{prob}",
                   "seconds": cell["seconds"],
                   "failures": cell["failures"],
                   "overhead_x": cell["seconds"] / base["seconds"],
                   "bit_equal": True}
            rows.append(row)
            csv.add(row["name"], cell["seconds"] / n_steps * 1e6,
                    f"failures={cell['failures']} "
                    f"overhead={row['overhead_x']:.2f}x bit_equal=True")
    finally:
        shutil.rmtree(root, ignore_errors=True)
    return rows


def _retry_grid(csv: Csv, fast: bool) -> list:
    from repro import atomics
    from repro.atomics import execute_until

    sizes = (8, 32) if fast else (8, 32, 128)
    policies = ("immediate", "shrink", "exponential")
    rows = []
    for n in sizes:
        for pol in policies:
            budget = n if pol != "shrink" else 8 * n
            t = atomics.AtomicTable(jnp.zeros((8,), jnp.int32))
            with telemetry.span("bench.retry", policy=pol, n=n) as sp:
                res = execute_until(t, _contended_make_ops(n),
                                    max_rounds=budget, policy=pol,
                                    sleep_fn=lambda d: None)
            dt = sp.wall_s
            assert res.pending.size == 0, f"{pol}/n{n}: unresolved ops"
            assert int(np.asarray(res.table.data)[0]) == n
            if pol != "shrink":      # the <= n acceptance bound
                assert res.n_rounds <= n, \
                    f"{pol}/n{n}: {res.n_rounds} rounds > n"
            row = {"name": f"retry/{pol}/n{n}", "n": n, "policy": pol,
                   "rounds": int(res.n_rounds),
                   "attempts": int(res.rounds.sum()), "seconds": dt,
                   "le_n_rounds": bool(res.n_rounds <= n)}
            rows.append(row)
            csv.add(row["name"], dt / max(1, res.n_rounds) * 1e6,
                    f"rounds={res.n_rounds} attempts={row['attempts']} "
                    f"le_n={row['le_n_rounds']}")
    # the shrink policy must actually buy fewer attempts at the top size
    top = max(sizes)
    att = {r["policy"]: r["attempts"] for r in rows if r["n"] == top}
    assert att["shrink"] < att["immediate"], \
        "shrink-batch spent no fewer attempts than immediate retry"
    return rows


def _sharded_row(csv: Csv, fast: bool) -> Dict:
    if fast:
        from repro import atomics
        from repro.atomics import execute_until
        n = 16
        from repro.sharding import make_mesh
        mesh = make_mesh((1,), ("dev",))
        data = jax.device_put(
            jnp.zeros((32,), jnp.int32),
            jax.sharding.NamedSharding(mesh,
                                       jax.sharding.PartitionSpec("dev")))
        with telemetry.span("bench.retry.sharded", n=n) as sp:
            res = execute_until(atomics.AtomicTable(data, axis="dev"),
                                _contended_make_ops(n), max_rounds=n)
        out = {"n": n, "n_rounds": int(res.n_rounds),
               "pending": int(res.pending.size),
               "attempts": int(res.rounds.sum()),
               "final": int(np.asarray(res.table.data)[0]),
               "seconds": sp.wall_s,
               "mesh": "1-device (fast)"}
    else:
        refuse_child_on_tpu("benchmarks.fault_recovery")
        env = dict(os.environ)
        env["PYTHONPATH"] = os.pathsep.join(
            [os.path.join(os.path.dirname(__file__), "..", "src")] +
            env.get("PYTHONPATH", "").split(os.pathsep))
        env["JAX_PLATFORMS"] = "cpu"
        proc = subprocess.run([sys.executable, "-c", _SHARDED_SCRIPT],
                              capture_output=True, text=True, env=env,
                              timeout=900)
        if proc.returncode != 0:
            raise RuntimeError(f"sharded retry subprocess failed:\n"
                               f"{proc.stderr[-2000:]}")
        line = [l for l in proc.stdout.splitlines()
                if l.startswith("RESULT:")][0]
        out = json.loads(line[len("RESULT:"):])
        out["mesh"] = "(2,4) 8-fake-device"
    assert out["pending"] == 0 and out["n_rounds"] <= out["n"], \
        f"sharded tier violated the <= n bound: {out}"
    assert out["final"] == out["n"]
    row = {"name": f"retry/sharded/n{out['n']}", **out}
    csv.add(row["name"], out["seconds"] / max(1, out["n_rounds"]) * 1e6,
            f"rounds={out['n_rounds']} mesh={out['mesh']} le_n=True")
    return row


def run(csv: Csv, fast: bool = False) -> None:
    results = {"fast": fast,
               "recovery": _recovery_grid(csv, fast),
               "retry": _retry_grid(csv, fast),
               "sharded": _sharded_row(csv, fast)}
    path = (RESULT_PATH.replace(".json", "_fast.json") if fast
            else RESULT_PATH)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(results, f, indent=2)
    csv.add("fault_recovery/artifact", 0.0, os.path.relpath(path))
