"""`bench/scopes.py` and the per-layer readers of the program's own spans
and scopes, on traces recorded on a TPU v5e (`record_fixtures.py`); and
every reading of the traces recorded before the program had them, as it
was before the readers came (``data/fixture_readings.json``)."""

import json
import os
from types import SimpleNamespace

import pytest

from bench import peaks, scopes
from bench import trace_reduce as tr
from bench.harness import Record, load_module

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(DATA)))
OLD_METRICS = ["bfs_roofline", "engine_roofline", "exchange.collective_ms",
               "host.enqueue_us", "idle_share.bfs", "idle_share.ops"]
NEW_METRICS = ["host.select_us", "host.dispatch_us", "engine.copy_ms", "engine.permute_ms",
               "engine.table_ms", "bfs.expand_ms", "bfs.claim_ms",
               "bfs.useful_edge_share"]
CTX = SimpleNamespace(peaks=peaks.TPU_V5E)


def read(name, trace, record):
    return load_module(ROOT, "metrics", name).read(trace, record, CTX)


# --- the traces recorded before: every reading as it was -----------------

with open(os.path.join(DATA, "fixture_readings.json")) as f:
    BEFORE = json.load(f)
#: the record the readings in ``fixture_readings.json`` were taken with
OLD_RECORD = dict(directed_edges=1 << 24, n=1 << 19,
                  enqueue_s=[1e-3, 2e-3], batch_ids=[0, 1, 2],
                  distinct=[10, 20, 30])


@pytest.mark.parametrize("fixture", sorted(BEFORE))
def test_old_fixtures_read_as_before(fixture):
    t = tr.load(os.path.join(DATA, f"{fixture}.xplane.pb"))
    want = BEFORE[fixture]
    rec = Record(extra=dict(OLD_RECORD))
    assert {m: read(m, t, rec) for m in OLD_METRICS} == want["metrics"]
    assert t.top_ops(10) == want["top_ops"]
    assert t.idle_gaps(10) == want["idle_gaps"]
    assert list(t.window) == want["window"]
    assert {k: len(v) for k, v in t.spans.items()} == want["spans"]
    assert len(t.host_events) == want["n_host"]


@pytest.mark.parametrize("fixture", sorted(BEFORE))
def test_new_readers_find_nothing_in_old_fixtures(fixture):
    t = scopes.load_trace(os.path.join(DATA, f"{fixture}.xplane.pb"))
    rec = Record(extra=dict(OLD_RECORD))
    assert {m: read(m, t, rec) for m in NEW_METRICS} == \
        {m: None for m in NEW_METRICS}


def test_scopes_clock_is_the_reductions_clock():
    """The same device ops at the same times as `trace_reduce` reads."""
    path = os.path.join(DATA, "bfs_1chip.xplane.pb")
    t, sc = tr.load(path), scopes.load(path)
    (dev,) = t.devices
    ops = sc.devices[dev.name]
    assert [(o.start, o.end, o.name) for o in ops] == dev.ops
    gather = [o for o in ops if o.name == "fusion.15"]
    assert gather and {o.scope for o in gather} == {
        "jit(_bfs_run)/while/body/gather"}
    assert {o.module for o in gather} == {"jit__bfs_run"}
    assert [o.holds_others for o in ops if o.name.startswith("while")] \
        == [True] * 4


# --- the traces recorded with the program's spans and scopes --------------

COUNTERS = os.path.join(DATA, "counters_scoped_1chip.xplane.pb")
BFS = os.path.join(DATA, "bfs_scoped_1chip.xplane.pb")
#: Graph500's symmetrised edge list at the fixture's scale 8, edge factor 16
BFS_RECORD = dict(directed_edges=2 * 16 << 8, n=1 << 8)


def recorded(name):
    with open(os.path.join(DATA, f"{name}.json")) as f:
        return {k: v["value"] for k, v in json.load(f)["metrics"].items()}


def test_counter_fixture_spans_nest_inside_each_batch():
    t, sc = scopes.load_trace(COUNTERS), scopes.load(COUNTERS)
    calls = sc.named("atomics.execute")
    assert calls and all(s.args == {"n": 64, "op": "faa", "backend": "sort"}
                         for s in calls)
    for name in ("atomics.select", "atomics.dispatch"):
        inner = sc.named(name)
        assert len(inner) == len(calls)
        assert all(c.start <= s.start and s.end <= c.end
                   for c, s in zip(calls, inner))
    assert all(s.args == {"backend": "sort"}
               for s in sc.named("atomics.dispatch"))
    batches = t.spans["bench.batch"]
    assert all(any(b <= c.start and c.end <= e for b, e in batches)
               for c in calls)


def test_counter_fixture_ops_carry_the_engine_scopes():
    ops = scopes.load(COUNTERS).devices["TPU:0"]
    found = {p for o in ops for p in o.scope.split("/")
             if p.startswith("rmw.")}
    assert found == {"rmw.sort", "rmw.gather", "rmw.scan", "rmw.scatter",
                     "rmw.unsort"}
    assert {o.module for o in ops} == {"jit_rmw_combining"}
    copies = [o for o in ops if scopes.is_copy(o)]
    assert copies and all(o.scope == "" for o in copies)
    share = scopes.scope_share([o for o in ops if not scopes.is_copy(o)],
                               "jit_rmw_combining", "rmw.")
    assert share > 0.95


def test_bfs_fixture_traversals_carry_their_counts():
    sc = scopes.load(BFS)
    done = sc.named("bfs.traversal")
    assert len(done) == 7
    for s in done:
        assert s.args["n"] == BFS_RECORD["n"] and s.args["op"] == "cas"
        assert 0 < s.args["edges_traversed"] <= BFS_RECORD["directed_edges"]
        assert s.args["levels"] >= 2
    ops = sc.devices["TPU:0"]
    assert scopes.scope_share(ops, "jit__bfs_run", "bfs.") > 0.95
    # the engine's scatter runs inside the level's claim
    assert all("bfs.claim" in o.scope.split("/") for o in ops
               if "rmw.scatter" in o.scope.split("/"))


@pytest.mark.parametrize("metric,fixture,extra", [
    ("host.select_us", "counters_scoped_1chip", {}),
    ("host.dispatch_us", "counters_scoped_1chip", {}),
    ("engine.copy_ms", "counters_scoped_1chip", {}),
    ("engine.permute_ms", "counters_scoped_1chip", {}),
    ("engine.table_ms", "counters_scoped_1chip", {}),
    ("bfs.expand_ms", "bfs_scoped_1chip", {}),
    ("bfs.claim_ms", "bfs_scoped_1chip", {}),
    ("bfs.useful_edge_share", "bfs_scoped_1chip", BFS_RECORD),
])
def test_readers_read_what_the_chip_run_printed(metric, fixture, extra):
    t = scopes.load_trace(os.path.join(DATA, f"{fixture}.xplane.pb"))
    got = read(metric, t, Record(extra=dict(extra)))
    assert got == pytest.approx(recorded(fixture)[metric], rel=1e-12)
    assert got > 0


def test_engine_copy_ms_is_the_copies_in_the_batches():
    """By hand, by another rule than the reader's: the engine program's ops
    with no name stack are the compiler's copies, and their time in the
    window's batches is the reading."""
    t, sc = scopes.load_trace(COUNTERS), scopes.load(COUNTERS)
    batches = scopes.spans_in_window(t, "bench.batch")
    bare = [o for o in sc.devices["TPU:0"]
            if o.module == "jit_rmw_combining" and not o.scope]
    assert bare and {o.category for o in bare} == {
        "data formatting", "copy-start", "copy-done"}
    want = sum(o.end - o.start for o in bare
               if any(b <= o.start and o.end <= e for b, e in batches))
    want *= 1e-6 / len(batches)
    assert read("engine.copy_ms", t, Record()) == pytest.approx(want)
    assert want > 0


def test_host_spans_split_the_eager_call():
    """The pick and the dispatch are disjoint parts of each call, so their
    readings add up to less than the call's own span a batch."""
    t = scopes.load_trace(COUNTERS)
    calls = scopes.host_us_per_batch(t, "atomics.execute")
    parts = [read(m, t, Record())
             for m in ("host.select_us", "host.dispatch_us")]
    assert all(p > 0 for p in parts) and sum(parts) < calls


def test_useful_edge_share_by_hand():
    t = scopes.load_trace(BFS)
    done = scopes.traversals(t)
    levels = sum(s.args["levels"] for s in done)
    edges = sum(s.args["edges_traversed"] for s in done)
    assert read("bfs.useful_edge_share", t, Record(extra=dict(BFS_RECORD))) \
        == pytest.approx(100 * edges / (levels * BFS_RECORD["directed_edges"]))


def test_per_level_times_add_up_to_the_loop():
    """expand + claim + frontier is the level loop's device time, and each
    part is its scope's ops, clipped to the traversals."""
    t, sc = scopes.load_trace(BFS), scopes.load(BFS)
    done = scopes.traversals(t)
    levels = sum(s.args["levels"] for s in done)
    ops = sc.devices["TPU:0"]
    spans = [(s.start, s.end) for s in done]
    parts = sum(scopes.leaf_time_s(ops, spans, lambda o, n=n:
                                   scopes.in_scope(o, n))
                for n in ("bfs.expand", "bfs.claim", "bfs.frontier"))
    body = scopes.leaf_time_s(ops, spans, lambda o: "/while/body/" in
                              "/" + o.scope + "/")
    assert parts == pytest.approx(body, rel=0.02)
    assert (read("bfs.expand_ms", t, Record()) +
            read("bfs.claim_ms", t, Record())) * levels * 1e-3 <= parts


def test_readers_on_a_trace_without_the_program():
    """A traced run of a program that opens no span and no scope reads
    nothing, and does not raise."""
    t = scopes.load_trace(os.path.join(DATA, "bfs_1chip.xplane.pb"))
    t.spans["bench.batch"] = t.spans["bench.root"]
    for m in NEW_METRICS:
        assert read(m, t, Record(extra=dict(BFS_RECORD))) is None


# --- finding the file of a trace -----------------------------------------

def test_readers_read_nothing_when_the_file_is_unknown():
    """A trace read by `trace_reduce.load` outside a run keeps no file."""
    t = tr.load(COUNTERS)
    assert scopes.of(t) is None
    for m in NEW_METRICS:
        assert read(m, t, Record(extra=dict(BFS_RECORD))) is None


def run_cell(tdir, summary, metric, trace=None):
    """Stands for `bench.harness.run_cell` at the point where it calls the
    readers: its trace directory in ``tdir``, its trace in ``summary``; the
    reader is given ``trace``, by default that summary."""
    return read(metric, summary if trace is None else trace, Record())


@pytest.mark.parametrize("metric", ["host.dispatch_us", "engine.table_ms"])
def test_readers_find_the_file_of_the_run_reading_them(metric):
    t = tr.load(COUNTERS)
    want = recorded("counters_scoped_1chip")[metric]
    assert run_cell(DATA + "/missing", t, metric) is None
    assert run_cell(COUNTERS, t, metric) == pytest.approx(want, rel=1e-12)
    # the run's file serves only the trace the run read
    assert run_cell(COUNTERS, t, metric, trace=tr.load(COUNTERS)) is None


def test_traced_run_reads_the_program_spans(tiny_root):
    """Through the harness itself (CPU, tiny sizes): the host readers find
    the run's own trace file; the CPU trace has no device ops to read."""
    from bench import harness
    out = harness.run_cell("counters-zipf-1m", 2**33 + 7, 0.2, True,
                           root=tiny_root, require_tpu=False,
                           log=lambda line: None)
    assert out["correct"] is True
    for m in ("host.select_us", "host.dispatch_us"):
        assert out["metrics"][m]["value"] > 0
