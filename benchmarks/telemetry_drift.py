"""Telemetry drift benchmark: the observability layer measuring itself.

Two deliverables, emitted to benchmarks/results/telemetry_drift.json
(--fast writes the *_fast.json variant):

  drift ratios       real instrumented traffic through all three selector
                     tiers — local engine (eager `atomics.execute` under
                     ``sync=True``: serialized / sort / onehot backends),
                     sharded exchange (one-round `execute_until` FAA on the
                     8-fake-device mesh, subprocess), and migration (both
                     reshard paths on the same mesh) — folded by
                     `telemetry.drift.aggregate` into per-(tier, choice,
                     op, size-bucket) measured/predicted ratios and the
                     `fit_spec_update` HardwareSpec proposal.
  overhead gate      eager-execute wall time with the stream enabled
                     (RingBuffer sink, no sync) vs disabled, < 5% at the
                     representative batch (n=4096, the drift capture's
                     largest) AND at jit steady-state (cached executions
                     run no instrumentation at all).  An eager size sweep
                     is reported alongside: below ~1k ops the jax CPU
                     dispatch floor (~70us) dominates and the instrument's
                     fixed ~2-5us Python cost reads as an inflated
                     percentage no production batch pays.

The drift ratios on this container are expected to be large for the local
tier (the engine constants price TPU-tier work; eager CPU dispatch costs
Python) — the point of the table is that the *loop is closed*: the numbers
are per-tier, reproducible, and `fit_spec_update` turns them into spec
corrections.  The overhead gate, by contrast, is a hard acceptance bound.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import Csv, refuse_child_on_tpu
from repro import atomics, telemetry
from repro.telemetry import drift as drift_lib

RESULT_PATH = os.path.join(os.path.dirname(__file__), "results",
                           "telemetry_drift.json")

#: ISSUE 7 acceptance: enabled-stream overhead on eager execute
OVERHEAD_GATE = 0.05

_SHARDED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro import atomics, telemetry

FAST = %(fast)r
OUT = %(out)r
from repro.sharding import make_mesh
mesh = make_mesh((2, 4), ("pod", "dev"))
m = 4096

def table():
    return atomics.AtomicTable(
        jax.device_put(jnp.zeros((m,), jnp.int32),
                       NamedSharding(mesh, P(("pod", "dev")))),
        axis=("pod", "dev"))

def faa_ops(n):
    rng = np.random.default_rng(n)
    def make_ops(slots, observed):
        if slots is None:
            return atomics.Faa(
                jnp.asarray(rng.integers(0, m, (n,)), jnp.int32),
                jnp.ones((n,), jnp.int32))
        return None
    return make_ops

sizes = (64, 512) if FAST else (64, 512, 4096)
for n in sizes:                      # warm the per-shape round compiles
    atomics.execute_until(table(), faa_ops(n), max_rounds=1)

telemetry.enable(telemetry.JsonlWriter(OUT), sync=True)
reps = 3 if FAST else 5
for n in sizes:
    for _ in range(reps):
        # FAA resolves in one round: each call = one sharded exchange with
        # a (predicted_s, measured_s) pair from the retry combinator
        atomics.execute_until(table(), faa_ops(n), max_rounds=1)

telemetry.disable()
# migration tier: both paths, several reps each
built = table()
for _ in range(2):                   # warm both migration compiles
    atomics.reshard.migrate(built, mesh, axis=("dev",),
                            replica_axes=("pod",), path="exchange")
    atomics.reshard.migrate(built, mesh, axis=("dev",),
                            replica_axes=("pod",), path="device_put")
telemetry.enable(telemetry.JsonlWriter(OUT + ".mig"), sync=True)
for _ in range(reps):
    atomics.reshard.migrate(built, mesh, axis=("dev",),
                            replica_axes=("pod",), path="exchange")
    atomics.reshard.migrate(built, mesh, axis=("dev",),
                            replica_axes=("pod",), path="device_put")
telemetry.disable()
print("RESULT:" + json.dumps({"ok": True}))
"""


def _local_capture(path: str, fast: bool) -> None:
    """Eager instrumented traffic across the local engine's backends."""
    m = 1024
    # n=4 exercises the serialized backend (it wins tiny batches)
    sizes = (4, 64, 512) if fast else (4, 64, 512, 4096)
    rng = np.random.default_rng(0)

    def batches(n):
        dup = jnp.asarray(rng.integers(0, 8, (n,)), jnp.int32)
        spread = jnp.asarray(rng.integers(0, m, (n,)), jnp.int32)
        ones = jnp.ones((n,), jnp.int32)
        return [
            atomics.Faa(spread, ones),               # large-m: onehot/sort
            atomics.Faa(dup, ones),                  # 8 hot slots: sort
            atomics.Cas(dup, ones, expected=jnp.zeros((), jnp.int32)),
        ]

    tbl = atomics.AtomicTable(jnp.zeros((m,), jnp.int32))
    for n in sizes:                  # warm primitive compiles un-instrumented
        for op in batches(n):
            atomics.execute(tbl, op)
    telemetry.enable(telemetry.JsonlWriter(path), sync=True)
    try:
        reps = 3 if fast else 5
        for n in sizes:
            for _ in range(reps):
                for op in batches(n):
                    atomics.execute(tbl, op)
    finally:
        telemetry.disable()


def _timed_pair(call, *, batch: int, n_batches: int) -> Tuple[float, float]:
    """(enabled_s, disabled_s) per call: min of per-batch means.  Each
    batch amortizes timer overhead, the min rejects scheduler noise (the
    standard microbenchmark floor), and enabled/disabled batches
    interleave so load drift hits both equally.  Raw ``perf_counter`` on
    purpose — measuring the instrumentation with `telemetry.span` would
    put the instrument inside its own measurement."""
    for _ in range(batch):               # warm
        call()
    ring = telemetry.RingBuffer(capacity=16)
    t_on: list = []
    t_off: list = []
    try:
        for _ in range(n_batches):
            telemetry.enable(ring)
            t0 = time.perf_counter()
            for _ in range(batch):
                call()
            t_on.append((time.perf_counter() - t0) / batch)
            telemetry.disable()
            t0 = time.perf_counter()
            for _ in range(batch):
                call()
            t_off.append((time.perf_counter() - t0) / batch)
    finally:
        telemetry.disable()
    return min(t_on), min(t_off)


#: overhead gate batch: the drift capture's largest size — eager calls
#: below ~1k ops sit at the jax CPU *dispatch floor* (~70us regardless of
#: n), where the instrument's fixed ~2-5us Python cost is an inflated
#: fraction of a cost that no production batch pays
_GATE_N = 4096


def _overhead(fast: bool) -> Dict[str, object]:
    """Eager-execute wall with the stream enabled (ring, no sync) vs off.

    Gates on two points; everything else in the sweep is informational:

    * eager at ``n=_GATE_N`` — the representative instrumented-dispatch
      workload (the drift capture's largest batch);
    * jit steady-state — the production path: cached executions of a
      jitted step run **no** instrumentation at all (events are
      trace-time-only), so the overhead there must be noise-level.
    """
    m = 1024
    rng = np.random.default_rng(1)
    tbl = atomics.AtomicTable(jnp.zeros((m,), jnp.int32))
    batch = 20
    n_batches = 8 if fast else 25
    sizes = (4, 512, _GATE_N) if fast else (4, 64, 512, _GATE_N)

    sweep = {}
    for n in sizes:
        op = atomics.Faa(jnp.asarray(rng.integers(0, m, (n,)), jnp.int32),
                         jnp.ones((n,), jnp.int32))

        def call(op=op):
            return jax.block_until_ready(
                atomics.execute(tbl, op).table.data)

        on, off = _timed_pair(call, batch=batch, n_batches=n_batches)
        sweep[n] = {"disabled_us": off * 1e6, "enabled_us": on * 1e6,
                    "overhead": on / off - 1.0}

    n = _GATE_N
    op = atomics.Faa(jnp.asarray(rng.integers(0, m, (n,)), jnp.int32),
                     jnp.ones((n,), jnp.int32))
    step = jax.jit(lambda data, i, v: atomics.execute(
        atomics.AtomicTable(data), atomics.Faa(i, v)).table.data)

    def jit_call():
        return jax.block_until_ready(step(tbl.data, op.indices, op.values))

    jit_on, jit_off = _timed_pair(jit_call, batch=batch,
                                  n_batches=n_batches)

    gate = sweep[_GATE_N]
    return {"gate_n": _GATE_N,
            "disabled_us": gate["disabled_us"],
            "enabled_us": gate["enabled_us"],
            "overhead": gate["overhead"],
            "jit_disabled_us": jit_off * 1e6,
            "jit_enabled_us": jit_on * 1e6,
            "jit_overhead": jit_on / jit_off - 1.0,
            "eager_sweep": {str(k): v for k, v in sweep.items()}}


def run(csv: Csv, fast: bool = False, out_path: str = RESULT_PATH
        ) -> Dict[str, object]:
    if fast and out_path == RESULT_PATH:
        # never clobber the committed full run with a CI smoke run
        out_path = RESULT_PATH.replace(".json", "_fast.json")
    tmp = tempfile.mkdtemp(prefix="telemetry_drift_")
    local_cap = os.path.join(tmp, "local.jsonl")
    sharded_cap = os.path.join(tmp, "sharded.jsonl")

    _local_capture(local_cap, fast)

    refuse_child_on_tpu("benchmarks.telemetry_drift")
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-c",
         _SHARDED_SCRIPT % {"fast": fast, "out": sharded_cap}],
        env=env, capture_output=True, text=True, timeout=1800,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    if proc.returncode != 0:
        raise RuntimeError(
            f"sharded capture subprocess failed:\n{proc.stderr[-2000:]}")

    events = telemetry.read_jsonl(local_cap)
    events += telemetry.read_jsonl(sharded_cap)
    events += telemetry.read_jsonl(sharded_cap + ".mig")
    stats = drift_lib.aggregate(events)
    rows = drift_lib.summarize(stats)
    fitted = drift_lib.fit_spec_update(stats)
    overhead = _overhead(fast)

    tiers = {r["tier"] for r in rows}
    for r in rows:
        csv.add(f"telemetry.drift.{r['tier']}.{r['choice']}."
                f"{r['op']}.{r['size_bucket']}",
                r["mean_measured_s"] * 1e6,
                f"pred={r['mean_predicted_s'] * 1e6:.3g}us "
                f"ratio={r['ratio']:.3g} n={r['n']}")
    csv.add("telemetry.overhead", overhead["enabled_us"],
            f"n={overhead['gate_n']} "
            f"disabled={overhead['disabled_us']:.0f}us "
            f"overhead={overhead['overhead'] * 100:.1f}pct "
            f"gate<{OVERHEAD_GATE * 100:.0f}pct")
    csv.add("telemetry.overhead.jit", overhead["jit_enabled_us"],
            f"disabled={overhead['jit_disabled_us']:.0f}us "
            f"overhead={overhead['jit_overhead'] * 100:.1f}pct "
            f"(cached executions: trace-time events only)")

    acceptance = (overhead["overhead"] < OVERHEAD_GATE
                  and overhead["jit_overhead"] < OVERHEAD_GATE
                  and {"local", "sharded", "migration"} <= tiers)
    out = {
        "fast": fast,
        "n_events": len(events),
        "drift": rows,
        "spec_update": fitted["fields"],
        "overhead": {**overhead, "gate": OVERHEAD_GATE},
        "tiers_covered": sorted(tiers),
        "acceptance_overhead_lt_gate_and_all_tiers": bool(acceptance),
    }
    assert acceptance, (
        f"telemetry drift acceptance failed: overhead="
        f"{overhead['overhead']:.3f} jit={overhead['jit_overhead']:.3f} "
        f"(gate {OVERHEAD_GATE}), tiers={sorted(tiers)}")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(out, f, indent=1)
    csv.add("telemetry_drift/artifact", 0.0, os.path.relpath(out_path))
    return out
