"""The trace reducer: interval arithmetic, and every number it reads from a
small trace recorded on a TPU v5e 2x2 (three steps of a sharded
fetch-and-add, collectives included), against values read by hand from the
same trace's Perfetto export."""

import os

import pytest

from bench import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_merge_and_overlap():
    merged = tr.merge([(5, 7), (0, 2), (1, 3), (7, 8), (10, 12)])
    assert merged == [(0, 3), (5, 8), (10, 12)]
    assert tr.overlap(merged, 0, 12) == 3 + 3 + 2
    assert tr.overlap(merged, 2, 6) == 1 + 1
    assert tr.overlap(merged, 8, 10) == 0
    assert tr.overlap([], 0, 5) == 0


def test_op_name():
    assert tr.op_name("%sort.0 = (s32[8]{0}) sort(s32[8]{0} %p)") == "sort.0"
    assert tr.op_name("fusion.3") == "fusion.3"
    assert tr.COLLECTIVE.search("all-to-all.2")
    assert tr.COLLECTIVE.search("all-reduce-start.1")
    assert not tr.COLLECTIVE.search("fusion.12")


def test_host_label_names_the_innermost_span_and_event():
    t = tr.Trace(window=(0, 100), devices=[], spans={},
                 host_events=[(0, 100, "bench.window"),
                              (10, 50, "bench.step"),
                              (12, 30, "PjitFunction(step)"),
                              (60, 70, "other")])
    assert t.host_label(20) == "bench.step > PjitFunction(step)"
    assert t.host_label(40) == "bench.step"
    assert t.host_label(65) == "other"
    assert t.host_label(90) == "outside benchmark spans"


# Read by hand from each trace's JSON export (``runsc.trace.json.gz``,
# microseconds), merging each chip's intervals of the ``XLA Ops`` and
# ``Async XLA Ops`` threads and clipping them to the ``bench.window`` span.
# The export rounds each event's times apart from the ``.xplane.pb``, so
# sums over a hundred short events agree to about 0.1 us.
BFS_1CHIP = dict(window_us=8390907.007, busy_us=8378022.665,
                 root_busy_us=[2094680.281, 2094356.058, 2094507.863,
                               2094478.462])
SHARDED_2X2 = dict(window_us=12314.820,
                   busy_us=[410.165, 406.365, 406.352, 404.131],
                   collective_us=[20.129, 16.340, 16.403, 13.390])
US = 1e-6


@pytest.fixture(scope="module")
def bfs_trace():
    return tr.load(os.path.join(DATA, "bfs_1chip.xplane.pb"))


@pytest.fixture(scope="module")
def sharded_trace():
    return tr.load(os.path.join(DATA, "sharded_2x2.xplane.pb"))


def test_one_chip_busy_idle_and_span_busy(bfs_trace):
    """Four BFS traversals at scale 19 on one TPU v5e."""
    t = bfs_trace
    assert [d.name for d in t.devices] == ["TPU:0"]
    (dev,) = t.devices
    assert t.window_s == pytest.approx(BFS_1CHIP["window_us"] * US, abs=1e-7)
    assert t.busy_s(dev) == pytest.approx(BFS_1CHIP["busy_us"] * US,
                                          abs=1e-7)
    assert t.idle_share(dev) == pytest.approx(
        1 - BFS_1CHIP["busy_us"] / BFS_1CHIP["window_us"], abs=1e-7)
    assert t.span_busy_s("bench.root", dev) == pytest.approx(
        [b * US for b in BFS_1CHIP["root_busy_us"]], abs=1e-7)
    assert t.collective_s(dev) == 0
    ops = dict(t.top_ops(3))
    assert max(ops, key=ops.get) == "jit__bfs_run/fusion.15"
    assert all(label.startswith("bench.root") for label, _ in t.idle_gaps(2))


def test_four_chip_busy_and_collective_time(sharded_trace):
    """Three steps of a sharded fetch-and-add on a 2x2 mesh: one all-to-all
    each way a step on every chip."""
    t = sharded_trace
    assert [d.name for d in t.devices] == [f"TPU:{i}" for i in range(4)]
    assert t.window_s == pytest.approx(SHARDED_2X2["window_us"] * US,
                                       abs=1e-7)
    for dev, busy, coll in zip(t.devices, SHARDED_2X2["busy_us"],
                               SHARDED_2X2["collective_us"]):
        assert t.busy_s(dev) == pytest.approx(busy * US, abs=2e-7)
        assert t.collective_s(dev) == pytest.approx(coll * US, abs=2e-7)
    assert t.mean_busy_s() == pytest.approx(
        sum(SHARDED_2X2["busy_us"]) / 4 * US, abs=2e-7)
    assert len(t.spans["bench.step"]) == 3
