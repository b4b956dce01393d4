"""repro.telemetry: stream mechanics, jit discipline, drift math, sinks."""

import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import atomics, telemetry
from repro.sharding import make_mesh
from repro.telemetry import drift


@pytest.fixture(autouse=True)
def _stream_off():
    """Every test starts and ends with the stream disabled."""
    telemetry.disable()
    yield
    telemetry.disable()


# ---------------------------------------------------------------------------
# Stream mechanics
# ---------------------------------------------------------------------------

def test_disabled_by_default_and_record_is_noop():
    assert not telemetry.enabled()
    telemetry.record("anything", x=1)          # must not raise, must not keep
    assert telemetry.sinks() == ()


def test_disabled_record_is_cheap():
    """The zero-overhead contract: a disabled record is one boolean check.
    Budget is deliberately loose (CI jitter) — 200k no-ops in under a
    second still rules out any per-call allocation/locking regression."""
    t0 = time.perf_counter()
    for _ in range(200_000):
        telemetry.record("noop", a=1, b=2.0)
    assert time.perf_counter() - t0 < 1.0


def test_ring_buffer_capture_and_restore():
    with telemetry.capture() as buf:
        assert telemetry.enabled()
        telemetry.record("ev", k=1)
        telemetry.record("ev", k=2)
    assert not telemetry.enabled()
    assert [e["k"] for e in buf.events] == [1, 2]
    assert all(e["event"] == "ev" and "t" in e for e in buf.events)


def test_ring_buffer_is_bounded():
    buf = telemetry.RingBuffer(capacity=4)
    with telemetry.capture(buf):
        for i in range(10):
            telemetry.record("ev", i=i)
    assert [e["i"] for e in buf.events] == [6, 7, 8, 9]


def test_capture_nests_and_restores_prior_sinks():
    outer = telemetry.RingBuffer()
    telemetry.enable(outer)
    with telemetry.capture() as inner:
        telemetry.record("both")
    telemetry.record("outer_only")
    assert [e["event"] for e in outer.events] == ["both", "outer_only"]
    assert [e["event"] for e in inner.events] == ["both"]


def test_jsonl_round_trip(tmp_path):
    path = str(tmp_path / "cap.jsonl")
    telemetry.enable(telemetry.JsonlWriter(path))
    telemetry.record("ev", i=np.int64(3), x=np.float32(0.5),
                     arr=np.arange(2), nested={"k": (1, 2)})
    telemetry.disable()                        # closes (and flushes) the file
    events = telemetry.read_jsonl(path)
    assert len(events) == 1
    ev = events[0]
    assert ev["event"] == "ev" and ev["i"] == 3
    assert ev["x"] == pytest.approx(0.5)
    assert ev["arr"] == [0, 1] and ev["nested"] == {"k": [1, 2]}


def test_broken_sink_never_breaks_the_instrumented_path():
    class Boom(telemetry.Sink):
        def emit(self, event):
            raise RuntimeError("sink died")
    good = telemetry.RingBuffer()
    telemetry.enable(Boom(), good)
    telemetry.record("ev")
    assert len(good.events) == 1               # later sinks still served


def test_counters_aggregate_numeric_fields():
    c = telemetry.Counters()
    with telemetry.capture(c):
        telemetry.record("ev", v=1.0, tag="a")
        telemetry.record("ev", v=3.0, tag="b")
        telemetry.record("other")
    s = c.summary()
    assert s["ev"]["count"] == 2 and s["other"]["count"] == 1
    v = s["ev"]["fields"]["v"]
    assert (v["n"], v["mean"], v["min"], v["max"]) == (2, 2.0, 1.0, 3.0)
    assert "tag" not in s["ev"]["fields"]      # strings are not aggregated


def test_span_measures_even_when_disabled():
    with telemetry.span("x") as sp:
        pass
    assert sp.wall_s is not None and sp.wall_s >= 0.0
    with telemetry.capture() as buf:
        with telemetry.span("x", step=3) as sp:
            pass
    (ev,) = buf.events
    assert ev["event"] == "x" and ev["step"] == 3 and ev["ok"] is True
    assert ev["wall_s"] == pytest.approx(sp.wall_s)


def test_span_records_failure_flag():
    with telemetry.capture() as buf:
        with pytest.raises(ValueError):
            with telemetry.span("x"):
                raise ValueError("boom")
    assert buf.events[0]["ok"] is False


def test_span_set_adds_end_counts_to_the_event():
    with telemetry.capture() as buf:
        with telemetry.span("x", n=4) as sp:
            sp.set(levels=7)
    (ev,) = buf.events
    assert ev["n"] == 4 and ev["levels"] == 7 and sp.fields["levels"] == 7


def test_span_records_nothing_when_the_stream_is_off():
    with telemetry.capture() as buf:
        pass
    with telemetry.span("x", n=4) as sp:
        sp.set(levels=7)
    assert len(buf) == 0 and telemetry.sinks() == ()
    assert sp.wall_s is not None


def _profiled(tmp_path, fn):
    """Run ``fn`` under the profiler; its result and the host events of
    the trace as ``{name: [stats dict, ...]}``."""
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = [os.path.join(d, f) for d, _, fs in os.walk(tmp_path)
               for f in fs if f.endswith(".xplane.pb")]
    events = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                events.setdefault(ev.name, []).append(dict(ev.stats))
    return out, events


def test_span_is_a_profiler_span_with_its_fields(tmp_path):
    def run():
        with telemetry.span("layer.phase", n=4, op="faa") as sp:
            sp.set(levels=7)
    _, events = _profiled(tmp_path, run)
    (stats,) = events["layer.phase"]
    assert stats == {"n": 4, "op": "faa", "levels": 7}


def test_eager_execute_spans_in_the_trace_and_results_unchanged(tmp_path):
    """`atomics.execute` is bit-identical with the profiler on and off, and
    its spans carry the batch size, the op and the backend picked."""
    from repro.core import rmw_engine
    tbl = atomics.AtomicTable(jnp.zeros((512,), jnp.int32))
    op = _faa(256, 512, seed=5)
    off = atomics.execute(tbl, op)
    on, events = _profiled(tmp_path, lambda: atomics.execute(tbl, op))
    for a, b in ((off.table.data, on.table.data), (off.fetched, on.fetched),
                 (off.success, on.success)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    picked = rmw_engine.select_backend("faa", 256, 512, dtype=jnp.int32)
    (ex,) = events["atomics.execute"]
    assert ex == {"n": 256, "op": "faa", "backend": picked}
    assert len(events["atomics.select"]) == 1
    assert events["atomics.dispatch"] == [{"backend": picked}]


def test_stats_path_spans_select_and_dispatch(tmp_path):
    tbl = atomics.AtomicTable(jnp.zeros((64,), jnp.int32))
    res, events = _profiled(tmp_path, lambda: atomics.execute(
        tbl, _faa(32, 64), collect_stats=True))
    assert res.stats is not None
    assert len(events["atomics.select"]) == 1
    (ex,) = events["atomics.execute"]
    assert events["atomics.dispatch"] == [{"backend": ex["backend"]}]


def test_no_span_inside_a_traced_function(tmp_path):
    @jax.jit
    def step(data, idx, vals):
        return atomics.execute(data, atomics.Faa(idx, vals)).table.data
    op = _faa(16, 32)
    _, events = _profiled(tmp_path, lambda: step(
        jnp.zeros((32,), jnp.int32), op.indices, op.values))
    assert not {"atomics.execute", "atomics.select",
                "atomics.dispatch"} & set(events)


def test_bfs_traversal_span_carries_its_counts(tmp_path):
    from repro.core import bfs
    src = np.array([0, 1, 1, 2, 3, 4], np.int32)
    dst = np.array([1, 0, 2, 1, 4, 3], np.int32)
    res, events = _profiled(tmp_path, lambda: bfs.bfs(src, dst, 5, root=0))
    (stats,) = events["bfs.traversal"]
    assert stats["levels"] == res.levels == 3
    assert stats["edges_traversed"] == res.edges_traversed


def test_enable_from_env(tmp_path, monkeypatch):
    monkeypatch.delenv(telemetry.TELEMETRY_ENV, raising=False)
    assert telemetry.enable_from_env() is False
    path = str(tmp_path / "env.jsonl")
    monkeypatch.setenv(telemetry.TELEMETRY_ENV, path)
    assert telemetry.enable_from_env() is True
    telemetry.record("ev")
    telemetry.disable()
    assert telemetry.read_jsonl(path)[0]["event"] == "ev"


# ---------------------------------------------------------------------------
# Instrumented atomics: decision events, jit discipline
# ---------------------------------------------------------------------------

def _faa(n, m, seed=0):
    rng = np.random.default_rng(seed)
    return atomics.Faa(jnp.asarray(rng.integers(0, m, (n,)), jnp.int32),
                       jnp.ones((n,), jnp.int32))


def test_eager_execute_emits_one_decision_event_with_measured_time():
    tbl = atomics.AtomicTable(jnp.zeros((64,), jnp.int32))
    with telemetry.capture(sync=True) as buf:
        atomics.execute(tbl, _faa(32, 64))
    (ev,) = [e for e in buf.events if e["event"] == "atomics.execute"]
    assert ev["tier"] == "local" and ev["traced"] is False
    assert ev["op"] == "faa" and ev["n"] == 32 and ev["m"] == 64
    assert ev["backend"] in ("serialized", "sort", "onehot", "pallas")
    assert ev["predicted_s"] > 0.0 and ev["measured_s"] > 0.0


def test_predicted_matches_the_selectors_own_choice():
    from repro.core import rmw_engine
    tbl = atomics.AtomicTable(jnp.zeros((256,), jnp.int32))
    op = _faa(128, 256)
    with telemetry.capture() as buf:
        atomics.execute(tbl, op)
    (ev,) = [e for e in buf.events if e["event"] == "atomics.execute"]
    sel = rmw_engine.select_backend_with_cost("faa", 128, 256, None,
                                              dtype=tbl.dtype)
    assert ev["backend"] == sel.choice
    assert ev["predicted_s"] == pytest.approx(sel.predicted_s)


def test_jit_retrace_discipline_no_duplicate_events():
    tbl_data = jnp.zeros((32,), jnp.int32)
    op = _faa(16, 32)

    @jax.jit
    def step(data, idx, vals):
        res = atomics.execute(atomics.AtomicTable(data),
                              atomics.Faa(idx, vals))
        return res.table.data
    with telemetry.capture() as buf:
        data = tbl_data
        for _ in range(5):                     # 1 compile + 4 cached calls
            data = step(data, op.indices, op.values)
    evs = [e for e in buf.events if e["event"] == "atomics.execute"]
    assert len(evs) == 1                       # trace-time only, once
    assert evs[0]["traced"] is True
    assert "measured_s" not in evs[0]          # no wall time inside a trace
    # a NEW shape retraces: exactly one more event
    op2 = _faa(8, 32)
    with telemetry.capture() as buf2:
        step(data, op2.indices, op2.values)
        step(data, op2.indices, op2.values)
    evs2 = [e for e in buf2.events if e["event"] == "atomics.execute"]
    assert len(evs2) == 1 and evs2[0]["n"] == 8


def test_instrumentation_changes_no_results():
    tbl = atomics.AtomicTable(jnp.zeros((64,), jnp.int32))
    op = _faa(48, 64, seed=3)
    base = atomics.execute(tbl, op)
    with telemetry.capture(sync=True):
        instr = atomics.execute(tbl, op)
    np.testing.assert_array_equal(np.asarray(base.table.data),
                                  np.asarray(instr.table.data))
    np.testing.assert_array_equal(np.asarray(base.fetched),
                                  np.asarray(instr.fetched))


def test_retry_rounds_and_done_histogram():
    tbl = atomics.AtomicTable(jnp.zeros((8,), jnp.int32))
    n = 5

    def make_ops(slots, observed):
        if slots is None:
            return atomics.Cas(jnp.zeros((n,), jnp.int32),
                               jnp.ones((n,), jnp.int32),
                               expected=jnp.zeros((n,), jnp.int32))
        return observed + 1
    with telemetry.capture() as buf:
        res = atomics.retry.execute_until(tbl, make_ops, max_rounds=n)
    assert res.success.all()
    rounds = [e for e in buf.events if e["event"] == "atomics.retry.round"]
    assert len(rounds) == res.n_rounds == n    # full contention: n rounds
    assert [e["pending"] for e in rounds] == [5, 4, 3, 2, 1]
    assert all(e["resolved"] == 1 and e["measured_s"] > 0 for e in rounds)
    (done,) = [e for e in buf.events if e["event"] == "atomics.retry.done"]
    assert done["n"] == n and done["unresolved"] == 0
    # op i wins on round i+1: one op per attempt-count 1..n
    assert done["round_histogram"] == [0] + [1] * n
    assert done["attempts"] == n * (n + 1) // 2


def test_reshard_migrate_event(monkeypatch):
    mesh = make_mesh((1,), ("dev",))
    data = jax.device_put(
        jnp.zeros((16,), jnp.int32),
        jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("dev")))
    tbl = atomics.AtomicTable(data, axis="dev")
    with telemetry.capture() as buf:
        atomics.reshard.migrate(tbl, mesh, path="device_put")
    (ev,) = [e for e in buf.events
             if e["event"] == "atomics.reshard.migrate"]
    assert ev["path"] == "device_put" and ev["tier"] == "migration"
    assert ev["n_slots"] == 16
    assert ev["measured_s"] > 0 and ev["predicted_s"] > 0


# ---------------------------------------------------------------------------
# Drift aggregation + spec fitting (pure math)
# ---------------------------------------------------------------------------

def _ev(tier, choice, op, n, pred, meas):
    key = "path" if tier == "migration" else \
        ("backend" if tier == "local" else "strategy")
    return {"event": ("atomics.reshard.migrate" if tier == "migration"
                      else "atomics.execute"),
            "tier": tier, key: choice, "op": op, "n": n,
            "predicted_s": pred, "measured_s": meas}


def test_drift_ratio_is_geometric_mean():
    # 2x slow and 2x fast must cancel exactly
    evs = [_ev("local", "sort", "faa", 64, 1e-4, 2e-4),
           _ev("local", "sort", "faa", 64, 1e-4, 5e-5)]
    stats = drift.aggregate(evs)
    (st,) = stats.values()
    assert st.n == 2
    assert st.ratio == pytest.approx(1.0)
    assert st.min_ratio == pytest.approx(0.5)
    assert st.max_ratio == pytest.approx(2.0)


def test_drift_grouping_and_skips():
    evs = [
        _ev("local", "sort", "faa", 64, 1e-4, 2e-4),
        _ev("local", "sort", "faa", 4096, 1e-4, 2e-4),   # other size bucket
        _ev("local", "serialized", "cas", 4, 1e-5, 1e-5),
        _ev("local", "sort", "faa", 64, None, 2e-4),     # unpriced: skipped
        {"event": "atomics.execute", "tier": "local", "backend": "sort",
         "op": "faa", "n": 64, "predicted_s": 1e-4, "traced": True},
        {"event": "train.step", "predicted_s": 1e-4, "measured_s": 1e-4},
    ]
    stats = drift.aggregate(evs)
    assert set(stats) == {("local", "sort", "faa", "2^6"),
                          ("local", "sort", "faa", "2^12"),
                          ("local", "serialized", "cas", "2^2")}


def test_size_bucket():
    assert drift.size_bucket(1) == "2^0"
    assert drift.size_bucket(8) == "2^3"
    assert drift.size_bucket(9) == "2^4"
    assert drift.size_bucket(None) == "?"


def test_fit_spec_update_direct_and_inverse():
    from repro.core.perf_model import cpu_default_spec
    spec = cpu_default_spec()
    evs = (
        # serialized 4x slow -> loop_step_s scales UP 4x
        [_ev("local", "serialized", "cas", 8, 1e-5, 4e-5)] * 4 +
        # device_put 2x slow -> host_roundtrip_Bps scales DOWN 2x
        [_ev("migration", "device_put", "-", 4096, 1e-3, 2e-3)] * 4
    )
    out = drift.fit_spec_update(drift.aggregate(evs), spec)
    f = out["fields"]
    assert f["loop_step_s"]["ratio"] == pytest.approx(4.0)
    assert f["loop_step_s"]["proposed"] == \
        pytest.approx(spec.loop_step_s * 4.0)
    assert f["host_roundtrip_Bps"]["proposed"] == \
        pytest.approx(spec.host_roundtrip_Bps / 2.0)
    assert out["spec"].loop_step_s == pytest.approx(spec.loop_step_s * 4.0)
    assert out["spec"].name == spec.name       # only constants move


def test_fit_spec_update_needs_min_samples():
    from repro.core.perf_model import cpu_default_spec
    evs = [_ev("local", "sort", "faa", 64, 1e-4, 2e-4)] * 2
    out = drift.fit_spec_update(drift.aggregate(evs), cpu_default_spec(),
                                min_samples=3)
    assert out["fields"] == {}


def test_fit_spec_update_per_field_floors_and_skipped():
    from repro.core.perf_model import cpu_default_spec
    spec = cpu_default_spec()
    evs = ([_ev("local", "serialized", "cas", 8, 1e-5, 2e-5)] * 5 +
           [_ev("local", "sort", "faa", 64, 1e-4, 3e-4)] * 2)
    stats = drift.aggregate(evs)
    # mapping floors: "*" default + a per-field override
    out = drift.fit_spec_update(stats, spec,
                                min_samples={"*": 2, "loop_step_s": 6})
    assert "sort_elem_pass_s" in out["fields"]          # 2 >= "*": 2
    assert out["skipped"]["loop_step_s"] == {"n": 5, "min_samples": 6}
    # an int floor still applies uniformly
    out2 = drift.fit_spec_update(stats, spec, min_samples=3)
    assert "loop_step_s" in out2["fields"]
    assert out2["skipped"]["sort_elem_pass_s"] == {"n": 2, "min_samples": 3}


def test_fit_spec_update_skips_unset_fields_with_reason():
    import dataclasses
    from repro.core.perf_model import cpu_default_spec
    spec = dataclasses.replace(cpu_default_spec(), loop_step_s=0.0)
    evs = [_ev("local", "serialized", "cas", 8, 1e-5, 2e-5)] * 4
    out = drift.fit_spec_update(drift.aggregate(evs), spec, min_samples=2)
    assert out["fields"] == {}
    assert out["skipped"]["loop_step_s"]["reason"] == "field unset on spec"


def test_report_build(tmp_path):
    from repro.telemetry.report import build_report, render_text
    evs = [_ev("local", "sort", "faa", 64, 1e-4, 2e-4)] * 3
    path = str(tmp_path / "cap.jsonl")
    with open(path, "w") as f:
        for e in evs:
            f.write(json.dumps(e) + "\n")
    report = build_report(telemetry.read_jsonl(path))
    assert report["n_events"] == 3
    assert report["events"]["atomics.execute"]["count"] == 3
    (row,) = report["drift"]
    assert row["ratio"] == pytest.approx(2.0)
    text = render_text(report)
    assert "atomics.execute" in text and "sort" in text


def test_report_surfaces_skipped_fields():
    from repro.telemetry.report import build_report, render_text
    evs = [_ev("local", "sort", "faa", 64, 1e-4, 2e-4)] * 2   # below floor
    report = build_report(evs)
    assert report["spec_update"] == {}
    assert report["spec_update_skipped"]["sort_elem_pass_s"]["n"] == 2
    text = render_text(report)
    assert "sort_elem_pass_s: skipped" in text


# ---------------------------------------------------------------------------
# add_sink / remove_sink and the ring crash-flush
# ---------------------------------------------------------------------------

def test_add_sink_widens_flags_and_remove_sink_resets():
    outer = telemetry.RingBuffer()
    telemetry.enable(outer, sync=True)
    tap = telemetry.RingBuffer()
    telemetry.add_sink(tap, sync=False)          # must NOT narrow sync
    assert telemetry.sync_enabled()
    telemetry.record("ev")
    assert len(outer.events) == 1 and len(tap.events) == 1
    assert telemetry.remove_sink(tap) is True
    assert telemetry.remove_sink(tap) is False   # already gone
    telemetry.record("ev")
    assert len(outer.events) == 2 and len(tap.events) == 1
    assert telemetry.remove_sink(outer) is True
    assert not telemetry.enabled()               # last sink out: stream off
    assert not telemetry.sync_enabled()


def test_add_sink_alone_enables_the_stream():
    tap = telemetry.RingBuffer()
    telemetry.add_sink(tap, sync=True)
    assert telemetry.enabled() and telemetry.sync_enabled()
    telemetry.remove_sink(tap)
    assert not telemetry.enabled()


def test_ring_events_and_flush_ring(tmp_path):
    assert telemetry.flush_ring() == 0           # no ring sink: no-op
    buf = telemetry.RingBuffer()
    telemetry.enable(buf)
    telemetry.record("a", i=1)
    telemetry.record("b", arr=np.arange(2))
    assert [e["event"] for e in telemetry.ring_events()] == ["a", "b"]
    path = str(tmp_path / "flush.jsonl")
    assert telemetry.flush_ring(path) == 2
    back = telemetry.read_jsonl(path)
    assert [e["event"] for e in back] == ["a", "b"]
    assert back[1]["arr"] == [0, 1]              # jsonable coercion applied
    # a JSONL-only stream has no ring to flush
    telemetry.disable()
    telemetry.enable(telemetry.JsonlWriter(str(tmp_path / "cap.jsonl")))
    telemetry.record("c")
    assert telemetry.ring_events() == [] and telemetry.flush_ring() == 0


def test_enable_from_env_ring_names_the_flush_path(tmp_path, monkeypatch):
    from repro.telemetry import core
    flush_to = str(tmp_path / "ring_tail.jsonl")
    monkeypatch.setattr(core, "_ring_flush_path", None)
    monkeypatch.setenv(telemetry.TELEMETRY_ENV, f"ring:{flush_to}")
    assert telemetry.enable_from_env() is True
    telemetry.record("crashy", step=3)
    assert telemetry.flush_ring() == 1           # default target = env path
    assert telemetry.read_jsonl(flush_to)[0]["event"] == "crashy"


def test_run_result_attaches_ring_tail():
    from repro.runtime.fault_tolerance import FaultConfig, run_with_recovery
    telemetry.enable(telemetry.RingBuffer())
    store = {}
    res = run_with_recovery(
        lambda s, x: x + 1, 0, 4,
        FaultConfig(checkpoint_every=2, backoff_base_s=0.0),
        lambda s, x: store.__setitem__(s, x),
        lambda: None)
    assert res.steps_done == 4
    assert any(e["event"] == "recovery.restore"
               for e in res.telemetry_ring)
    telemetry.disable()
    # without a ring sink the field is simply empty — no mode check needed
    res2 = run_with_recovery(
        lambda s, x: x + 1, 0, 2,
        FaultConfig(checkpoint_every=2, backoff_base_s=0.0),
        lambda s, x: None, lambda: None)
    assert res2.telemetry_ring == []


def test_fatal_fault_flushes_the_ring_to_disk(tmp_path, monkeypatch):
    from repro.runtime.fault_tolerance import (FatalFault, FaultConfig,
                                               run_with_recovery)
    from repro.telemetry import core
    flush_to = str(tmp_path / "postmortem.jsonl")
    monkeypatch.setattr(core, "_ring_flush_path", None)
    monkeypatch.setenv(telemetry.TELEMETRY_ENV, f"ring:{flush_to}")
    telemetry.enable_from_env()

    def dying_step(step, state):
        telemetry.record("train.step", step=step)
        if step == 2:
            raise FatalFault("chip gone for good")
        return state + 1

    with pytest.raises(FatalFault):
        run_with_recovery(
            dying_step, 0, 6,
            FaultConfig(checkpoint_every=2, backoff_base_s=0.0),
            lambda s, x: None, lambda: None)
    # the last-N events landed on disk before the fault propagated
    events = telemetry.read_jsonl(flush_to)
    assert any(e["event"] == "train.step" and e["step"] == 2
               for e in events)
    assert any(e["event"] == "recovery.fault" and e["fatal"]
               for e in events)


# ---------------------------------------------------------------------------
# Sharded tier: exactly one decision event per call site (8 fake devices)
# ---------------------------------------------------------------------------

_SHARDED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro import atomics, telemetry
from repro.sharding import shard_map_compat

from repro.sharding import make_mesh
mesh = make_mesh((2, 4), ("pod", "dev"))
m_local, n_per = 8, 4
idx = jnp.arange(8 * n_per, dtype=jnp.int32).reshape(8, n_per) % (8 * m_local)
vals = jnp.ones((8, n_per), jnp.int32)

def body(t, i, v):
    tbl = atomics.AtomicTable(t, axis=("pod", "dev"))
    res = atomics.execute(tbl, atomics.Faa(i, v))
    return res.table.data, res.fetched

fn = jax.jit(shard_map_compat(
    body, mesh,
    (P(("pod", "dev")), P(("pod", "dev")), P(("pod", "dev"))),
    (P(("pod", "dev")), P(("pod", "dev")))))

tab = jax.device_put(jnp.zeros((8 * m_local,), jnp.int32),
                     NamedSharding(mesh, P(("pod", "dev"))))
buf = telemetry.RingBuffer()
telemetry.enable(buf)
out, _ = fn(tab, idx.reshape(-1), vals.reshape(-1))   # compile: traces once
for _ in range(4):                                    # cached: no events
    out, _ = fn(out, idx.reshape(-1), vals.reshape(-1))
evs = [e for e in buf.events if e["event"] == "atomics.execute"]
decision = {k: evs[0][k] for k in
            ("tier", "traced", "strategy", "n", "m", "n_shards")} if evs else {}
pred = evs[0].get("predicted_s") if evs else None
print("RESULT:" + json.dumps({
    "n_events": len(evs), "decision": decision,
    "predicted_positive": bool(pred and pred > 0),
    "total": int(np.asarray(out).sum())}))
"""


def test_sharded_execute_emits_one_decision_event_per_call_site():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-c", _SHARDED_SCRIPT], env=env,
        capture_output=True, text=True, timeout=600,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = [l for l in proc.stdout.splitlines()
            if l.startswith("RESULT:")][0]
    out = json.loads(line[len("RESULT:"):])
    # shard_map traces the body ONCE: one decision event for the whole
    # 8-device mesh on compile, zero for the 4 cached executions
    assert out["n_events"] == 1, out
    d = out["decision"]
    assert d["tier"] == "sharded" and d["traced"] is True
    assert d["n_shards"] == 8 and d["m"] == 64
    assert d["strategy"] in ("oneshot", "hierarchical", "naive", "dense")
    assert out["predicted_positive"] is True
    assert out["total"] == 5 * 32               # results unchanged
