"""The contention observatory (PR 10): `collect_stats=` across tiers.

The load-bearing contracts:

* stats are a pure observer — results bit-identical with the pass on or
  off, on the local engine tier and the 8-fake-device sharded exchange;
* the numbers are exact — distinct/max/histogram/top-k agree with a host
  ``np.bincount`` of the same batch, per-exchange-level in/out counts are
  monotone with level 0 = the issued batch;
* `execute_until` feeds the tuning estimator from the device counts when
  one is active (same site keys as the host ``np.unique`` path, which is
  skipped entirely), and surfaces the round-0 stats on `RetryResult`;
* the telemetry plumbing: one ``contention.stats`` event per collected
  batch at a sync boundary, aggregated into the report's contention
  section; ring flushes land under `telemetry_dir`, not the CWD.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import atomics, telemetry
from repro.atomics import retry as retry_mod
from repro.atomics import stats as stats_mod
from repro.atomics.stats import HIST_BINS, TOPK, ContentionStats


def _np_stats(idx, m):
    occ = np.bincount(np.asarray(idx), minlength=m)
    hist = np.zeros(HIST_BINS, np.int64)
    for o in occ[occ > 0]:
        hist[min(int(np.floor(np.log2(o))), HIST_BINS - 1)] += 1
    return occ, hist


# ---------------------------------------------------------------------------
# the stats kernels themselves
# ---------------------------------------------------------------------------

def test_stats_from_occupancy_matches_numpy():
    m = 97
    rng = np.random.default_rng(0)
    idx = rng.integers(0, m, 513).astype(np.int32)
    occ, hist = _np_stats(idx, m)
    st = stats_mod.stats_from_occupancy(jnp.asarray(occ, jnp.int32),
                                        jnp.int32(idx.size))
    assert int(st.n_ops) == idx.size
    assert int(st.distinct_slots) == int((occ > 0).sum())
    assert int(st.max_occupancy) == int(occ.max())
    assert np.asarray(st.occupancy_hist).tolist() == hist.tolist()
    # top-k: counts are the k largest occupancies, slots actually hold them
    counts = np.asarray(st.topk_counts)
    slots = np.asarray(st.topk_slots)
    assert counts.tolist() == sorted(occ, reverse=True)[:TOPK]
    for s, c in zip(slots, counts):
        assert occ[s] == c


def test_topk_pads_with_minus_one_below_k_slots():
    occ = np.zeros(16, np.int32)
    occ[3], occ[11] = 5, 2
    slots, counts = stats_mod.topk_hot(jnp.asarray(occ))
    assert slots.tolist()[:2] == [3, 11]
    assert counts.tolist() == [5, 2] + [0] * (TOPK - 2)
    assert slots.tolist()[2:] == [-1] * (TOPK - 2)


def test_hist_buckets_are_log2():
    occ = np.array([1, 2, 3, 4, 7, 8, 0, 0], np.int32)
    hist = np.asarray(stats_mod.occupancy_hist(jnp.asarray(occ)))
    assert hist[0] == 1            # occupancy 1
    assert hist[1] == 2            # 2-3
    assert hist[2] == 2            # 4-7
    assert hist[3] == 1            # 8-15
    assert hist.sum() == 6         # unoccupied slots counted nowhere


def test_pallas_kernel_slot_occupancy_matches_bincount():
    from repro.kernels.rmw import ops as kops
    m = 300
    rng = np.random.default_rng(1)
    idx = rng.integers(-3, m + 5, 1000).astype(np.int32)  # some OOR
    occ = np.asarray(kops.slot_occupancy(jnp.asarray(idx), m))
    valid = idx[(idx >= 0) & (idx < m)]
    assert occ.tolist() == np.bincount(valid, minlength=m).tolist()


# ---------------------------------------------------------------------------
# execute(): bit identity + exactness, local tier
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.int32, jnp.float32])
def test_execute_collect_stats_bit_identical_local(dtype):
    m = 128
    rng = np.random.default_rng(2)
    idx = jnp.asarray(rng.integers(0, m, 700), jnp.int32)
    vals = jnp.asarray(rng.integers(-4, 5, 700), dtype)
    tbl = atomics.AtomicTable(jnp.zeros((m,), dtype))
    for op in (atomics.Faa(idx, vals),
               atomics.Cas(idx, vals,
                           expected=jnp.zeros((700,), dtype))):
        r_off = atomics.execute(tbl, op)
        r_on = atomics.execute(tbl, op, collect_stats=True)
        assert np.array_equal(np.asarray(r_off.table.data),
                              np.asarray(r_on.table.data))
        assert np.array_equal(np.asarray(r_off.fetched),
                              np.asarray(r_on.fetched))
        assert np.array_equal(np.asarray(r_off.success),
                              np.asarray(r_on.success))
        assert r_off.stats is None
        occ, _ = _np_stats(idx, m)
        st = r_on.stats
        assert isinstance(st, ContentionStats)
        assert int(np.asarray(st.distinct_slots)) == int((occ > 0).sum())
        assert int(np.asarray(st.max_occupancy)) == int(occ.max())
        assert int(np.asarray(st.n_ops)) == 700
        assert np.asarray(st.level_ops_in).size == 0   # local tier: L = 0


def test_execute_sequence_collects_one_stats_per_op():
    tbl = atomics.AtomicTable(jnp.zeros((16,), jnp.int32))
    ops = [atomics.Faa(jnp.zeros((4,), jnp.int32), jnp.ones((4,), jnp.int32)),
           atomics.Faa(jnp.arange(4, dtype=jnp.int32),
                       jnp.ones((4,), jnp.int32))]
    res = atomics.execute(tbl, ops, collect_stats=True)
    assert isinstance(res.stats, tuple) and len(res.stats) == 2
    assert int(np.asarray(res.stats[0].distinct_slots)) == 1
    assert int(np.asarray(res.stats[1].distinct_slots)) == 4
    assert atomics.execute(tbl, ops).stats is None


def test_sync_mode_emits_one_contention_event():
    tbl = atomics.AtomicTable(jnp.zeros((8,), jnp.int32))
    op = atomics.Faa(jnp.zeros((6,), jnp.int32), jnp.ones((6,), jnp.int32))
    with telemetry.capture(sync=True) as buf:
        atomics.execute(tbl, op, collect_stats=True)
        atomics.execute(tbl, op)                     # off: no event
    evs = [e for e in buf.events if e.get("event") == "contention.stats"]
    assert len(evs) == 1
    assert evs[0]["distinct_slots"] == 1 and evs[0]["max_occupancy"] == 6
    assert evs[0]["tier"] == "local" and evs[0]["op"] == "faa"


# ---------------------------------------------------------------------------
# execute_until: device-fed estimator, host-unique skip, RetryResult.stats
# ---------------------------------------------------------------------------

def _cas_loop(n=24, m=8, collect=None):
    idx = np.asarray(np.arange(n) % 4, np.int32)

    def make_ops(slots, observed):
        if slots is None:
            return atomics.Cas(jnp.asarray(idx), jnp.ones((n,), jnp.int32),
                               expected=jnp.zeros((n,), jnp.int32))
        return jnp.asarray(np.asarray(observed) + 1)

    return atomics.execute_until(
        atomics.AtomicTable(jnp.zeros((m,), jnp.int32)), make_ops,
        max_rounds=n, collect_stats=collect)


def test_retry_stats_none_by_default_without_controller():
    res = _cas_loop()
    assert res.success.all() and res.stats is None


def test_retry_collect_stats_explicit_true():
    res = _cas_loop(collect=True)
    assert res.success.all()
    assert int(np.asarray(res.stats.distinct_slots)) == 4
    assert int(np.asarray(res.stats.max_occupancy)) == 6
    # bit identity against the off path
    ref = _cas_loop(collect=False)
    assert ref.stats is None
    assert np.array_equal(np.asarray(res.table.data),
                          np.asarray(ref.table.data))
    assert np.array_equal(res.rounds, ref.rounds)


def test_controller_auto_feeds_estimator_from_device(monkeypatch):
    """Estimator active -> device stats on, host np.unique never runs."""
    from repro.tuning import SpecController, TuningConfig, site_key

    def boom(x):
        raise AssertionError("host np.unique path must be skipped when "
                             "device stats feed the estimator")

    monkeypatch.setattr(retry_mod, "_host_distinct", boom)
    with SpecController(TuningConfig()) as ctrl:
        res = _cas_loop()
        assert res.stats is not None
        assert ctrl.estimator.n_updates_device >= 1
        key = site_key("cas", "local", 8, 24)
        assert ctrl.estimator.raw(key) is not None
        # round-0 distinct = 4 contended slots; the CAS second observation
        # agrees, so the EWMA sits exactly at 4
        assert ctrl.estimator.raw(key) == pytest.approx(4.0)


def test_host_fallback_sites_match_device_sites():
    """Satellite key-stability: the host and device observation paths must
    produce identical site keys (and here, identical EWMA values)."""
    from repro.tuning import SpecController, TuningConfig
    with SpecController(TuningConfig()) as ctrl:
        _cas_loop(collect=False)                 # host np.unique path
        host_sites = ctrl.estimator.sites()
        assert ctrl.estimator.n_updates_host >= 1
    with SpecController(TuningConfig()) as ctrl:
        _cas_loop(collect=None)                  # auto -> device
        device_sites = ctrl.estimator.sites()
        assert ctrl.estimator.n_updates_device >= 1
    assert set(host_sites) == set(device_sites)
    assert host_sites == device_sites            # same EWMA values too


def test_host_unique_skipped_when_nothing_consumes_it(monkeypatch):
    """No estimator, no telemetry: round 0 must not pay the host pass."""
    calls = []
    monkeypatch.setattr(retry_mod, "_host_distinct",
                        lambda x: calls.append(1) or int(np.unique(x).size))
    res = _cas_loop()
    assert res.success.all() and calls == []
    with telemetry.capture():
        _cas_loop()                              # telemetry alone consumes it
    assert calls == [1]


def test_retry_emits_contention_event_once_under_sync(monkeypatch):
    from repro.tuning import SpecController, TuningConfig
    with telemetry.capture(sync=True) as buf:
        with SpecController(TuningConfig()):
            _cas_loop()
    evs = [e for e in buf.events if e.get("event") == "contention.stats"]
    assert len(evs) == 1                         # no double emit
    assert evs[0]["distinct_slots"] == 4


def test_estimator_state_roundtrip_with_device_fed_sites(tmp_path):
    """Satellite: snapshot()/restore() through SpecController state_path
    when the sites were fed from on-device counts."""
    from repro.tuning import SpecController, TuningConfig, site_key
    path = str(tmp_path / "tuning_state.json")
    cfg = TuningConfig()
    key = site_key("cas", "local", 8, 24)
    with SpecController(cfg, state_path=path) as ctrl:
        _cas_loop()                              # auto -> device feed
        assert ctrl.estimator.n_updates_device >= 1
        fed = ctrl.estimator.raw(key)
        assert fed is not None
    with SpecController(cfg, state_path=path) as ctrl2:
        assert ctrl2.estimator.raw(key) == fed
        # and the restored site keeps serving hints to the same site key
        assert ctrl2.estimator.hint(key) == 4


# ---------------------------------------------------------------------------
# telemetry plumbing: ring flush location + report section
# ---------------------------------------------------------------------------

def test_ring_flush_lands_under_telemetry_dir(tmp_path, monkeypatch):
    monkeypatch.setenv(telemetry.TELEMETRY_DIR_ENV,
                       str(tmp_path / "run_artifacts"))
    telemetry.enable(telemetry.RingBuffer())
    try:
        telemetry.record("crashy", step=1)
        assert telemetry.flush_ring() == 1
    finally:
        telemetry.disable()
    target = tmp_path / "run_artifacts" / "repro_telemetry_ring.jsonl"
    assert target.exists()                       # dir auto-created
    assert telemetry.read_jsonl(str(target))[0]["event"] == "crashy"
    assert not os.path.exists("repro_telemetry_ring.jsonl")


def test_telemetry_dir_default_is_artifacts(monkeypatch):
    monkeypatch.delenv(telemetry.TELEMETRY_DIR_ENV, raising=False)
    assert telemetry.telemetry_dir() == os.path.join("artifacts",
                                                     "telemetry")


def test_report_contention_section():
    from repro.telemetry.report import build_report, render_text
    evs = [
        {"event": "contention.stats", "tier": "local", "op": "faa",
         "n_ops": 64, "distinct_slots": 8, "max_occupancy": 16,
         "occupancy_hist": [0, 0, 0, 0, 8], "topk_slots": [3, 5],
         "topk_counts": [16, 12], "level_ops_in": [], "level_ops_out": []},
        {"event": "contention.stats", "tier": "local", "op": "faa",
         "n_ops": 64, "distinct_slots": 10, "max_occupancy": 8,
         "occupancy_hist": [0, 0, 0, 10], "topk_slots": [5, 9],
         "topk_counts": [8, 7], "level_ops_in": [], "level_ops_out": []},
        {"event": "contention.stats", "tier": "sharded", "op": "cas",
         "n_ops": 128, "distinct_slots": 2, "max_occupancy": 64,
         "occupancy_hist": [], "topk_slots": [], "topk_counts": [],
         "level_ops_in": [128, 64], "level_ops_out": [64, 2]},
    ]
    rep = build_report(evs, fit=False)
    rows = {(r["tier"], r["op"]): r for r in rep["contention"]}
    local = rows[("local", "faa")]
    assert local["batches"] == 2 and local["n_ops"] == 128
    assert local["mean_distinct"] == 9.0
    assert local["max_occupancy"] == 16
    assert local["occupancy_hist"] == [0, 0, 0, 10, 8]
    # hot slots merged across batches, max count kept per slot
    assert local["hot_slots"][0] == {"slot": 3, "count": 16}
    assert {h["slot"] for h in local["hot_slots"]} == {3, 5, 9}
    sharded = rows[("sharded", "cas")]
    assert sharded["level_efficiency"] == [0.5, round(2 / 64, 4)]
    text = render_text(rep)
    assert "contention (contention.stats events" in text
    assert "128->64" in text


# ---------------------------------------------------------------------------
# sharded tier (8 fake devices, subprocess)
# ---------------------------------------------------------------------------

_SHARDED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro import atomics

from repro.sharding import make_mesh
mesh = make_mesh((2, 4), ("pod", "dev"))
m = 256
n = 512
rng = np.random.default_rng(11)
idx = jnp.asarray(rng.integers(0, m, (n,)), jnp.int32)
vals = jnp.asarray(rng.integers(-3, 4, (n,)), jnp.int32)

def table():
    return atomics.AtomicTable(
        jax.device_put(jnp.zeros((m,), jnp.int32),
                       NamedSharding(mesh, P(("pod", "dev")))),
        axis=("pod", "dev"))

def run(collect):
    def make_ops(slots, observed):
        if slots is None:
            return atomics.Faa(idx, vals)
        return None
    return atomics.execute_until(table(), make_ops, max_rounds=1,
                                 collect_stats=collect)

r_off = run(False)
r_on = run(True)
st = r_on.stats
occ = np.bincount(np.asarray(idx), minlength=m)
out = {
    "bit_identical": bool(
        np.array_equal(np.asarray(r_off.table.data),
                       np.asarray(r_on.table.data))
        and np.array_equal(r_off.fetched, r_on.fetched)),
    "off_stats_none": r_off.stats is None,
    "distinct_ok": int(np.asarray(st.distinct_slots)) == int((occ > 0).sum()),
    "max_ok": int(np.asarray(st.max_occupancy)) == int(occ.max()),
    "n_ops": int(np.asarray(st.n_ops)),
    "level_in": np.asarray(st.level_ops_in).tolist(),
    "level_out": np.asarray(st.level_ops_out).tolist(),
    "topk_ok": all(occ[s] == c
                   for s, c in zip(np.asarray(st.topk_slots),
                                   np.asarray(st.topk_counts)) if s >= 0),
}
print("RESULT:" + json.dumps(out))
"""


def test_sharded_8dev_stats_bit_identical_and_exact():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [os.path.abspath("src")] +
                   os.environ.get("PYTHONPATH", "").split(os.pathsep)))
    proc = subprocess.run([sys.executable, "-c", _SHARDED_SCRIPT],
                          capture_output=True, text=True, env=env,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = [l for l in proc.stdout.splitlines()
            if l.startswith("RESULT:")][0]
    out = json.loads(line[len("RESULT:"):])
    assert out["bit_identical"] and out["off_stats_none"]
    assert out["distinct_ok"] and out["max_ok"] and out["topk_ok"]
    assert out["n_ops"] == 512
    # per-level efficiency: level 0 admits the whole batch; combining
    # never grows the op count on the way up
    assert out["level_in"], "sharded stats must report exchange levels"
    assert out["level_in"][0] == 512
    assert all(o <= i for i, o in zip(out["level_in"], out["level_out"]))
