"""The program's own names in a profiler trace (``*.xplane.pb``).

`bench.trace_reduce` reads the trace through `jax.profiler.ProfileData`,
which gives each event's name and times only.  The names the program puts
there itself sit in the XSpace protobuf's stats, read here with the
generated `bench.xplane_pb2`:

* each device operation's event metadata carries ``tf_op``, its JAX name
  stack, which holds the ``jax.named_scope`` names the program opens inside
  its jitted functions (``jit(rmw_combining)/rmw.sort/argsort``), and
  ``hlo_category`` (``copy``, ``loop fusion`` ...);
* each host span the program opens with `repro.telemetry.span` is an event
  whose stats are the span's fields (``atomics.execute`` with ``n``,
  ``op``, ``backend``; ``bfs.traversal`` with ``levels``).

Times are in nanoseconds on the trace's clock, computed as ProfileData
computes them, so they line up with `bench.trace_reduce.Trace`.  Parsed
after the window of a ``--trace 1`` run only.
"""

from __future__ import annotations

import bisect
import functools
import os
import re
import sys
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from bench import trace_reduce

#: a program span: dotted lower-case words (``atomics.select``), not the
#: benchmark's own ``bench.*`` spans nor the runtime's events
PROGRAM_SPAN = re.compile(r"^(?!bench\.)[a-z][a-z0-9_]*(\.[a-z0-9_]+)+$")


@dataclass
class Op:
    """One synchronous device operation."""
    start: int
    end: int
    name: str
    scope: str          # the JAX name stack (``tf_op``), "" when it has none
    category: str       # ``hlo_category``
    module: str         # the program it ran in, ``jit_rmw_combining``
    holds_others: bool  # a while loop or conditional around other ops


@dataclass
class Span:
    """One host span of the program, with its fields."""
    start: int
    end: int
    name: str
    args: Dict[str, object] = field(default_factory=dict)


@dataclass
class Scopes:
    devices: Dict[str, List[Op]]
    spans: List[Span]

    def named(self, name: str, lo: float = float("-inf"),
              hi: float = float("inf")) -> List[Span]:
        """Spans called ``name`` that lie wholly in ``[lo, hi]``."""
        return [s for s in self.spans
                if s.name == name and lo <= s.start and s.end <= hi]


def in_scope(op: Op, scope: str) -> bool:
    """True when ``scope`` is one of the names on the op's stack."""
    return scope in op.scope.split("/")


def leaf_time_s(ops: Sequence[Op], spans: Sequence[Tuple[float, float]],
                keep) -> float:
    """Seconds of the ops that ``keep`` selects, clipped to the union of
    ``spans``; an op that holds others is left out for its parts."""
    merged = trace_reduce.merge(spans)
    if not merged:
        return 0.0
    starts = [s for s, _ in merged]
    total = 0.0
    for op in ops:
        if op.holds_others or not keep(op):
            continue
        i = bisect.bisect_right(starts, op.start) - 1
        j = max(i, 0)
        while j < len(merged) and merged[j][0] < op.end:
            s, e = merged[j]
            total += max(0.0, min(e, op.end) - max(s, op.start))
            j += 1
    return total * 1e-9


def _stat_value(stat, names: Dict[int, str]):
    kind = stat.WhichOneof("value")
    if kind is None:
        return None
    value = getattr(stat, kind)
    if kind == "ref_value":
        return names.get(value, "")
    if kind == "bytes_value":
        return value.hex()
    return value


def _stats(stats, names: Dict[int, str]) -> Dict[str, object]:
    return {names.get(s.metadata_id, str(s.metadata_id)):
            _stat_value(s, names) for s in stats}


def _device_ops(plane) -> List[Op]:
    names = {k: v.name for k, v in plane.stat_metadata.items()}
    meta = {}
    for key, md in plane.event_metadata.items():
        st = _stats(md.stats, names)
        name = md.display_name or trace_reduce.op_name(md.name)
        meta[key] = (name, str(st.get("tf_op", "")).rstrip(":"),
                     str(st.get("hlo_category", "")))
    modules: List[Tuple[int, int, str]] = []
    raw: List[Tuple[int, int, int]] = []
    for line in plane.lines:
        if line.name == trace_reduce.MODULE_LINE:
            for ev in line.events:
                s = line.timestamp_ns + ev.offset_ps // 1000
                name = plane.event_metadata[ev.metadata_id].name
                modules.append((s, s + ev.duration_ps // 1000,
                                trace_reduce._MODULE_HASH.sub("", name)))
        elif line.name == trace_reduce.OP_LINES[0]:
            for ev in line.events:
                s = line.timestamp_ns + ev.offset_ps // 1000
                raw.append((s, s + ev.duration_ps // 1000, ev.metadata_id))
    modules.sort()
    raw.sort(key=lambda o: (o[0], -o[1]))
    starts = [m[0] for m in modules]
    ops = []
    for j, (s, e, key) in enumerate(raw):
        holds = j + 1 < len(raw) and raw[j + 1][0] < e and raw[j + 1][1] <= e
        i = bisect.bisect_right(starts, s) - 1
        module = modules[i][2] if i >= 0 and s < modules[i][1] else "?"
        name, scope, category = meta.get(key, ("?", "", ""))
        ops.append(Op(s, e, name, scope, category, module, holds))
    return ops


def _host_spans(plane) -> List[Span]:
    names = {k: v.name for k, v in plane.stat_metadata.items()}
    out = []
    for line in plane.lines:
        for ev in line.events:
            md = plane.event_metadata[ev.metadata_id]
            if not PROGRAM_SPAN.match(md.name):
                continue
            s = line.timestamp_ns + ev.offset_ps // 1000
            args = _stats(md.stats, names)
            args.update(_stats(ev.stats, names))
            out.append(Span(s, s + ev.duration_ps // 1000, md.name, args))
    out.sort(key=lambda sp: (sp.start, -sp.end))
    return out


@functools.lru_cache(maxsize=2)
def _load(path: str, mtime: float) -> Scopes:
    from bench import xplane_pb2

    space = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    devices: Dict[str, List[Op]] = {}
    spans: List[Span] = []
    for plane in space.planes:
        if plane.name.startswith(trace_reduce.DEVICE_PREFIX):
            devices[plane.name[len("/device:"):]] = _device_ops(plane)
        elif plane.name == trace_reduce.HOST_PLANE:
            spans = _host_spans(plane)
    return Scopes(devices, spans)


def load(path: str) -> Scopes:
    """The device ops and program spans of the trace at ``path`` (a file or
    a trace directory); parsed once per file."""
    path = trace_reduce.find_xplane(path)
    return _load(path, os.path.getmtime(path))


def load_trace(path: str) -> "trace_reduce.Trace":
    """`trace_reduce.load`, noting on the trace the file it came from, so
    that `of` finds its scopes."""
    path = trace_reduce.find_xplane(path)
    trace = trace_reduce.load(path)
    trace.xplane = path
    return trace


def of(trace) -> Optional[Scopes]:
    """The scopes and spans of the file ``trace`` was read from; None when
    that file is unknown.

    `trace_reduce.Trace` does not keep its file.  `load_trace` notes it on
    the trace.  In a ``--trace 1`` run the readers are called from
    `bench.harness.run_cell`, which reads the trace directory ``tdir`` into
    ``summary``: the file is found there, on the call stack, when that
    summary is this trace."""
    path = getattr(trace, "xplane", "")
    frame = sys._getframe(1)
    while not path and frame is not None:
        local = frame.f_locals
        if (frame.f_code.co_name == "run_cell"
                and local.get("summary") is trace
                and isinstance(local.get("tdir"), str)):
            try:
                path = trace_reduce.find_xplane(local["tdir"])
            except FileNotFoundError:
                return None
        frame = frame.f_back
    return load(path) if path else None


# ---------------------------------------------------------------------------
# What the per-layer readers share
# ---------------------------------------------------------------------------

def spans_in_window(trace, name: str) -> List[Tuple[float, float]]:
    """The benchmark's spans ``name`` that lie wholly in the window."""
    lo, hi = trace.window
    return [(s, e) for s, e in trace.spans.get(name, [])
            if lo <= s and e <= hi]


def host_us_per_batch(trace, name: str):
    """Host microseconds per batch in the program's span ``name``: the sum
    over the spans inside the batches (``bench.batch``) wholly inside the
    window, over the batch count; None with nothing to read."""
    batches = spans_in_window(trace, "bench.batch")
    sc = of(trace) if batches else None
    if sc is None:
        return None
    lo, hi = batches[0][0], batches[-1][1]
    inside = [s for s in sc.named(name, lo, hi)
              if any(b <= s.start and s.end <= e for b, e in batches)]
    if not inside:
        return None
    return sum(s.end - s.start for s in inside) * 1e-3 / len(batches)


def first_device_ops(trace, scopes: Optional[Scopes]) -> List[Op]:
    if not trace.devices or scopes is None:
        return []
    return scopes.devices.get(trace.devices[0].name, [])


def is_copy(op: Op) -> bool:
    """A copy (``copy.N``, ``copy-start``, ``copy-done``): the compiler's
    own, so it carries no name stack (``hlo_category`` data formatting)."""
    return op.name.startswith("copy")


def scope_family(op: Op, prefix: str) -> bool:
    """True when a name on the op's stack starts with ``prefix``."""
    return any(part.startswith(prefix) for part in op.scope.split("/"))


def modules_with(ops: Sequence[Op], prefix: str) -> set:
    """The programs that hold an op scoped ``prefix*``."""
    return {op.module for op in ops if scope_family(op, prefix)}


def scope_share(ops: Sequence[Op], module: str, prefix: str) -> float:
    """Share of program ``module``'s device time (ops that hold others left
    out) in ops scoped ``prefix*``; 0 when it never ran."""
    total = inside = 0
    for op in ops:
        if op.module != module or op.holds_others:
            continue
        total += op.end - op.start
        if scope_family(op, prefix):
            inside += op.end - op.start
    return inside / total if total else 0.0


def traversals(trace) -> List[Span]:
    """The program's ``bfs.traversal`` spans wholly inside the window that
    carry their level count."""
    sc = of(trace)
    if sc is None:
        return []
    return [s for s in sc.named("bfs.traversal", *trace.window)
            if "levels" in s.args and "edges_traversed" in s.args]


def per_level_ms(trace, scope: str):
    """Device milliseconds per level in ``scope`` over the traversals
    wholly inside the window; None with nothing to read."""
    done = traversals(trace)
    levels = sum(int(s.args["levels"]) for s in done)
    if not levels:
        return None
    ops = first_device_ops(trace, of(trace))
    s = leaf_time_s(ops, [(t.start, t.end) for t in done],
                    lambda op: in_scope(op, scope))
    return 1e3 * s / levels if s > 0 else None
