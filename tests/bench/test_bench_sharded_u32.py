"""The sharded counter cell ``counters4-zipf-256k`` from the committed
benchmark files: its driver at a tiny size on four fake CPU devices (the
program correct, the control and each planted fault not), its uint32 key
chooser, and its per-layer readers: on the 2x2 TPU v5e trace recorded
before the program had its scopes, and by hand on a built trace."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bench import peaks, scopes
from bench import trace_reduce as tr
from bench.harness import Record, load_module
from bench.traffic import zipf
from shardedroot import CELL, ROOT, make_u32_root

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
METRICS = ["exchange.collective_ms", "exchange.precombine_ms",
           "exchange.resolve_ms", "exchange_roofline", "idle_share.exchange"]
FAULTS = ["state_unchanged", "half_batch", "answer_altered",
          "exchange_left_out"]

SCRIPT = r"""
import os, sys, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path[:0] = [sys.argv[1], os.path.join(sys.argv[1], "src")]
import jax, jax.numpy as jnp
from bench import faults, harness
from repro import atomics
root, cell, out = sys.argv[2], sys.argv[3], {}


def fault(kind):
    # faults.counter_fault's, for uint32 ids: an id of 2^32 - 1 is out of
    # range of the 4096-slot table, as -1 is of an int32 one
    real = atomics.execute

    def broken(table, op, **kw):
        if kind == "half_batch":
            n = op.indices.shape[0]
            keep = jnp.arange(n) < n // 2
            idx = jnp.where(keep, op.indices, jnp.uint32(2**32 - 1))
            res = real(table, type(op)(idx, op.values), **kw)
            return faults._result(res.table.data,
                                  jnp.where(keep, res.fetched, 0), res)
        if kind == "exchange_left_out":
            m_local = table.data.shape[0]
            rank = jax.lax.axis_index(table.axis).astype(jnp.uint32)
            local = op.indices // m_local == rank
            idx = jnp.where(local, op.indices - rank * m_local,
                            jnp.uint32(m_local))
            res = real(atomics.AtomicTable(table.data),
                       type(op)(idx.astype(jnp.int32), op.values))
            return faults._result(res.table.data,
                                  jnp.where(local, res.fetched, 0), res)
        raise ValueError(kind)
    return faults.swapped(atomics, "execute", broken)


def go(mode, trace=False):
    if mode == "program":
        ctx = None
    elif mode == "control":
        ctx = faults.sharded_control()
    elif mode in ("fault:half_batch", "fault:exchange_left_out"):
        ctx = fault(mode.split(":")[1])
    else:
        ctx = faults.counter_fault(mode.split(":")[1])
    seed = 2**32 + 3 if mode == "program" else 5
    if ctx is None:
        r = harness.run_cell(cell, seed, 0.3, trace, root=root,
                             require_tpu=False, log=lambda s: None)
    else:
        with ctx:
            r = harness.run_cell(cell, seed, 0.2, trace, root=root,
                                 require_tpu=False, log=lambda s: None)
    return [r["correct"], r["checks"], sorted(r["metrics"]),
            r["device"]["count"]]


out["program"] = go("program")
out["traced"] = go("program", trace=True)
for mode in ["control"] + ["fault:" + k for k in sys.argv[4].split(",")]:
    out[mode] = go(mode)
print("RESULT:" + json.dumps(out))
"""


@pytest.fixture(scope="module")
def four_cpu(tmp_path_factory):
    """The cell on four fake CPU devices, in a child process (the device
    count is fixed when JAX starts)."""
    root = make_u32_root(tmp_path_factory.mktemp("u32_root"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", SCRIPT, ROOT, root, CELL,
                           ",".join(FAULTS)],
                          capture_output=True, text=True, env=env,
                          timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT:")][0]
    return json.loads(line[len("RESULT:"):])


def test_sharded_u32_cell_is_correct_with_its_metrics(four_cpu):
    correct, checks, metrics, count = four_cpu["program"]
    assert correct is True and count == 4
    assert metrics == ["ops_per_s", "setup_s"]
    assert checks == {"table_diff": {"value": 0, "limit": 0},
                      "fetched_diff": {"value": 0, "limit": 0}}


def test_sharded_u32_traced_cpu_run_reads_no_device_metric(four_cpu):
    """A CPU trace has no TPU planes: the readers read nothing and do not
    raise, and the run stays correct."""
    correct, _, metrics, _ = four_cpu["traced"]
    assert correct is True and metrics == []


@pytest.mark.parametrize("mode", ["control"] + [f"fault:{k}"
                                               for k in FAULTS])
def test_sharded_u32_control_and_faults_are_not_correct(four_cpu, mode):
    correct, checks, _, _ = four_cpu[mode]
    assert correct is False
    assert any(v["value"] > v["limit"] for v in checks.values())


# --- the uint32 key chooser ------------------------------------------------

zipf_keys = load_module(ROOT, "drivers", "counters_sharded_u32").zipf_keys


@pytest.mark.parametrize("recordcount", [1000, 1 << 30, 2**31 - 1])
def test_u32_chooser_draws_the_int32_chooser_keys(recordcount):
    a = zipf.scrambled_zipf_keys(np.random.default_rng(9), 20000,
                                 recordcount)
    b = zipf_keys(np.random.default_rng(9), 20000, recordcount)
    assert b.dtype == np.uint32
    np.testing.assert_array_equal(a.astype(np.int64), b.astype(np.int64))


def test_u32_chooser_reaches_past_2_31_at_2_32():
    keys = zipf_keys(np.random.default_rng(9), 200000, 1 << 32)
    assert keys.dtype == np.uint32
    high = np.count_nonzero(keys >= 1 << 31) / keys.size
    assert 0.45 < high < 0.55               # the hash spreads the ranks
    _, counts = np.unique(keys, return_counts=True)
    assert counts.max() / keys.size == pytest.approx(1 / zipf.ZETAN,
                                                     rel=0.1)


def test_u32_chooser_refuses_past_2_32():
    with pytest.raises(ValueError):
        zipf_keys(np.random.default_rng(0), 4, (1 << 32) + 1)


# --- the readers ---------------------------------------------------------

CTX = type("Ctx", (), {"peaks": peaks.TPU_V5E})()


def read(name, trace, record):
    return load_module(ROOT, "metrics", name).read(trace, record, CTX)


def test_readers_on_the_unscoped_2x2_trace():
    """The trace recorded before the program had its scopes: the collective
    and idle readers read what the existing readers read there; the scope
    readers, and the roofline without its record, read nothing."""
    with open(os.path.join(DATA, "fixture_readings.json")) as f:
        before = json.load(f)["sharded_2x2"]["metrics"]
    t = scopes.load_trace(os.path.join(DATA, "sharded_2x2.xplane.pb"))
    got = {m: read(m, t, Record()) for m in METRICS}
    assert got == {"exchange.collective_ms":
                   before["exchange.collective_ms"],
                   "exchange.precombine_ms": None,
                   "exchange.resolve_ms": None,
                   "exchange_roofline": None,
                   "idle_share.exchange": before["idle_share.ops"]}


def test_readers_by_hand_on_a_built_trace(monkeypatch):
    """Two chips, two steps of 10 ms each: the scope readers take the
    busier chip's time in their scope per step, the roofline that chip's
    least bytes over its busy time, the idle share the mean of both."""
    ms = 1_000_000                                   # ns
    steps = [(0, 10 * ms), (10 * ms, 20 * ms)]
    busy = {"TPU:0": [(1 * ms, 9 * ms), (11 * ms, 19 * ms)],
            "TPU:1": [(1 * ms, 5 * ms), (11 * ms, 15 * ms)]}
    devices = [tr.Device(name, busy=iv) for name, iv in busy.items()]
    t = tr.Trace(window=(0, 20 * ms), devices=devices,
                 spans={"bench.step": steps}, host_events=[])

    def op(s, e, scope):
        return scopes.Op(s * ms, e * ms, "fusion", scope, "", "jit_step",
                         False)
    ops = {"TPU:0": [op(1, 3, "exchange.precombine"),
                     op(3, 9, "exchange.resolve/rmw.scatter"),
                     op(11, 13, "exchange.precombine"),
                     op(13, 19, "exchange.resolve")],
           "TPU:1": [op(1, 4, "exchange.precombine"),
                     op(4, 5, "exchange.resolve"),
                     op(11, 14, "exchange.precombine"),
                     op(14, 15, "exchange.resolve")]}
    monkeypatch.setattr(scopes, "of", lambda trace: scopes.Scopes(ops, []))
    extra = {"batch_ids": [0, 1], "ops_per_chip": 1000,
             "owner_distinct": [[100, 50], [300, 70]],
             "shard_of_device": {"TPU:0": 0, "TPU:1": 1}}
    rec = Record(extra=extra)
    assert read("exchange.precombine_ms", t, rec) == pytest.approx(3.0)
    assert read("exchange.resolve_ms", t, rec) == pytest.approx(6.0)
    need = 2 * 1000 * 12 + 8 * (100 + 300)           # chip 0, the busier
    assert read("exchange_roofline", t, rec) == pytest.approx(
        100 * need / peaks.TPU_V5E.hbm_bytes_per_s / 16e-3)
    assert read("idle_share.exchange", t, rec) == pytest.approx(
        100 * ((1 - 16 / 20) + (1 - 8 / 20)) / 2)
