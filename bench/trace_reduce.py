"""Reduce a JAX profiler trace (``*.xplane.pb``) to the benchmark's numbers.

Read with `jax.profiler.ProfileData`.  On a TPU each chip is a plane named
``/device:TPU:<i>`` whose line ``XLA Ops`` holds one event per operation
and ``Async XLA Ops`` the span of each asynchronous copy or collective from
its start to its done; ``XLA Modules`` holds one event per program run.
Host threads are lines of the plane ``/host:CPU``; the benchmark's own spans
(`jax.profiler.TraceAnnotation`, names starting with ``bench.``) lie on the
thread that runs the benchmark.  Device and host events share one clock, in
nanoseconds from the start of the trace.

* busy: the union of the intervals in which an operation, synchronous or
  asynchronous, runs on a device, clipped to the window;
* window: the host span ``bench.window`` (else the extent of the device
  operations);
* busy inside a span: busy time that falls within each instance of a host
  span;
* collective time: the union of the intervals of all-to-all, all-gather,
  all-reduce, reduce-scatter and collective-permute operations;
* idle gaps: the stretches of the window in which a device runs nothing,
  each named by the innermost benchmark span and the innermost other host
  event around its middle.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Sequence, Tuple

Interval = Tuple[float, float]

DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OP_LINES = ("XLA Ops", "Async XLA Ops")
MODULE_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
#: op names spell the opcode with - or _ (``all-reduce.1``, ``all_to_all.9``)
COLLECTIVE = re.compile(r"all[-_]to[-_]all|all[-_]gather|all[-_]reduce|"
                        r"reduce[-_]scatter|collective[-_]permute")
_MODULE_HASH = re.compile(r"\(\d+\)$")


def merge(intervals: Sequence[Interval]) -> List[Interval]:
    """Sorted union of intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def overlap(merged: List[Interval], start: float, end: float) -> float:
    """Length of ``[start, end)`` covered by sorted disjoint ``merged``."""
    i = max(bisect.bisect_right(merged, (start, float("inf"))) - 1, 0)
    total = 0.0
    while i < len(merged) and merged[i][0] < end:
        s, e = merged[i]
        total += max(0.0, min(e, end) - max(s, start))
        i += 1
    return total


def op_name(event_name: str) -> str:
    """``%sort.0 = (s32[...]) sort(...)`` -> ``sort.0``."""
    head = event_name.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


@dataclass
class Device:
    name: str
    ops: List[Tuple[float, float, str]] = field(default_factory=list)
    async_ops: List[Tuple[float, float, str]] = field(default_factory=list)
    modules: List[Tuple[float, float, str]] = field(default_factory=list)
    busy: List[Interval] = field(default_factory=list)
    collective: List[Interval] = field(default_factory=list)


@dataclass
class Trace:
    window: Interval
    devices: List[Device]
    spans: Dict[str, List[Interval]]
    host_events: List[Tuple[float, float, str]]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self, device: Device) -> float:
        return overlap(device.busy, *self.window) * 1e-9

    def mean_busy_s(self) -> float:
        return sum(self.busy_s(d) for d in self.devices) / len(self.devices)

    def idle_share(self, device: Device) -> float:
        """1 - busy / window, as a fraction."""
        return 1.0 - self.busy_s(device) / self.window_s

    def span_busy_s(self, span: str, device: Device) -> List[float]:
        """Device busy seconds inside each instance of host span ``span``
        that lies wholly in the window."""
        lo, hi = self.window
        return [overlap(device.busy, s, e) * 1e-9
                for s, e in self.spans.get(span, []) if lo <= s and e <= hi]

    def collective_s(self, device: Device) -> float:
        return overlap(device.collective, *self.window) * 1e-9

    def top_ops(self, k: int = 10) -> List[List]:
        """Synchronous operations by device seconds inside the window, mean
        over devices, named ``<program>/<op>``.  An operation that holds
        others (a while loop, a conditional) is left out for its parts."""
        lo, hi = self.window
        total: Dict[str, float] = {}
        for d in self.devices:
            starts = [m[0] for m in d.modules]
            for j, (s, e, name) in enumerate(d.ops):
                if e <= lo or s >= hi:
                    continue
                if j + 1 < len(d.ops) and d.ops[j + 1][0] < e \
                        and d.ops[j + 1][1] <= e:
                    continue
                i = bisect.bisect_right(starts, s) - 1
                mod = d.modules[i][2] if i >= 0 and s < d.modules[i][1] \
                    else "?"
                key = f"{mod}/{name}"
                total[key] = total.get(key, 0.0) + (min(e, hi) - max(s, lo))
        n = len(self.devices)
        ranked = sorted(total.items(), key=lambda kv: -kv[1])[:k]
        return [[name, ns * 1e-9 / n] for name, ns in ranked]

    def idle_gaps(self, k: int = 10) -> List[List]:
        """The ``k`` longest stretches with no operation on a device, each
        labelled by what the host was doing at its middle."""
        lo, hi = self.window
        gaps = []
        for di, d in enumerate(self.devices):
            t = lo
            for s, e in d.busy:
                if e <= lo:
                    continue
                if s >= hi:
                    break
                if s > t:
                    gaps.append((s - t, t, di))
                t = max(t, e)
            if t < hi:
                gaps.append((hi - t, t, di))
        gaps.sort(key=lambda g: -g[0])
        out = []
        for length, start, di in gaps[:k]:
            label = self.host_label(start + length / 2)
            if len(self.devices) > 1:
                label = f"{self.devices[di].name}:{label}"
            out.append([label, length * 1e-9])
        return out

    def host_label(self, t: float) -> str:
        span = inner = None
        for s, e, name in self.host_events:
            if s > t:
                break
            if e < t:
                continue
            if name.startswith(SPAN_PREFIX):
                if name != WINDOW_SPAN:
                    span = name
            else:
                inner = name
        parts = [p for p in (span, inner) if p]
        return " > ".join(parts) if parts else "outside benchmark spans"


def find_xplane(path: str) -> str:
    """The newest ``*.xplane.pb`` under a trace directory (or the file)."""
    if os.path.isfile(path):
        return path
    found = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return max(found, key=os.path.getmtime)


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(path))
    devices: List[Device] = []
    host_lines = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            dev = Device(plane.name[len("/device:"):])
            for line in plane.lines:
                if line.name in OP_LINES:
                    ops = dev.ops if line.name == OP_LINES[0] \
                        else dev.async_ops
                    for ev in line.events:
                        s = ev.start_ns
                        ops.append((s, s + ev.duration_ns, op_name(ev.name)))
                elif line.name == MODULE_LINE:
                    for ev in line.events:
                        s = ev.start_ns
                        dev.modules.append(
                            (s, s + ev.duration_ns,
                             _MODULE_HASH.sub("", ev.name)))
            dev.ops.sort(key=lambda o: (o[0], -o[1]))
            dev.modules.sort()
            every = dev.ops + dev.async_ops
            dev.busy = merge([(s, e) for s, e, _ in every])
            dev.collective = merge([(s, e) for s, e, n in every
                                    if COLLECTIVE.search(n)])
            devices.append(dev)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                evs = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                       for ev in line.events]
                if any(n.startswith(SPAN_PREFIX) for _, _, n in evs):
                    host_lines.append(evs)
    devices.sort(key=lambda d: int(d.name.rsplit(":", 1)[-1]))
    host = sorted(e for line in host_lines for e in line)
    spans: Dict[str, List[Interval]] = {}
    for s, e, name in host:
        if name.startswith(SPAN_PREFIX):
            spans.setdefault(name, []).append((s, e))
    if WINDOW_SPAN in spans:
        window = spans[WINDOW_SPAN][0]
    else:
        starts = [d.ops[0][0] for d in devices if d.ops]
        ends = [max(e for _, e, _ in d.ops) for d in devices if d.ops]
        window = (min(starts), max(ends)) if starts else (0.0, 0.0)
    return Trace(window, devices, spans, host)
