"""Run one cell of the benchmark once and print its result line.

A cell is an entry of ``workloads`` in ``BENCHMARK.json``.  Everything
about it is found by name, so a later change adds a cell by adding files:

* ``bench/workloads/<cell>.json``  the traffic: driver, sizes, mix, window;
* ``bench/configs/<config>.json``  the deployment (the file ``configs``
  names in ``BENCHMARK.json``);
* ``bench/drivers/<driver>.py``    the measuring loop of one kind of cell;
* ``bench/metrics/<metric>.py``    one reader per per-layer metric.

A driver module has three functions:

``setup(ctx) -> state``
    build the inputs on the device from ``ctx.seed`` and warm up every
    program the window runs (counted as set-up);
``window(state, seconds) -> Record``
    the measured loop, closed-loop, for ``seconds``;
``check(state, record) -> [Check]``
    after the window and the memory reading: copy the outputs to the host,
    free the program's state, and compare with the plain reference.

A metric module has ``read(trace, record, ctx) -> float | None``; ``None``
(nothing to read) leaves the metric out of the line.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class BenchError(RuntimeError):
    """The run cannot produce a result (no chip, a cell that is not there)."""


@dataclass
class Check:
    """One number compared with its limit; the run is correct only when
    every number is at or below its limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclass
class Record:
    """What a window measured."""
    attempted: int = 0
    failed: int = 0
    window_s: float = 0.0
    e2e: Dict[str, float] = field(default_factory=dict)
    extra: Dict[str, Any] = field(default_factory=dict)


@dataclass
class Context:
    root: str
    name: str
    entry: dict          # the cell's entry in BENCHMARK.json
    cell: dict           # bench/workloads/<cell>.json
    config: dict         # the configuration's file
    seed: int
    devices: list
    peaks: Any = None


def span(name: str):
    """A host span in the profiler's trace (nearly free when not tracing)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


def read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_spec(root: str) -> dict:
    path = os.path.join(root, "BENCHMARK.json")
    if not os.path.isfile(path):
        raise BenchError(f"no BENCHMARK.json in {root}")
    return read_json(path)


def load_cell(root: str, spec: dict, name: str):
    """(entry, cell, config) of the cell ``name``."""
    entries = {w["name"]: w for w in spec["workloads"]}
    if name not in entries:
        raise BenchError(f"no workload {name!r}; have {sorted(entries)}")
    entry = entries[name]
    cell = read_json(os.path.join(root, "bench", "workloads", f"{name}.json"))
    configs = {c["name"]: c for c in spec["configs"]}
    config = read_json(os.path.join(root, configs[entry["config"]]["file"]))
    return entry, cell, config


def applies(metric: dict, name: str) -> bool:
    return name in metric.get("workloads", [name])


def load_module(root: str, kind: str, name: str):
    path = os.path.join(root, "bench", kind, f"{name}.py")
    if not os.path.isfile(path):
        raise BenchError(f"no {kind[:-1]} {name!r} at {path}")
    mod_name = f"bench_{kind}_{name}".replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@contextlib.contextmanager
def count_compiles():
    """Counts programs traced or compiled while the block runs."""
    import jax
    seen = {"n": 0}

    def listen(event, duration, **_):
        if event in ("/jax/core/compile/backend_compile_duration",
                     "/jax/core/compile/jaxpr_trace_duration"):
            seen["n"] += 1
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        yield seen
    finally:
        from jax._src import monitoring
        monitoring.unregister_event_duration_listener(listen)


def devices_for(chips: int, require_tpu: bool) -> list:
    import jax
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise BenchError("JAX sees no TPU; nothing was run")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees "
                         f"{len(devices)}")
    return devices[:chips]


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             root: str = REPO, require_tpu: bool = True,
             t0: Optional[float] = None, trace_dir: Optional[str] = None,
             log=None) -> dict:
    """Run cell ``name`` once and return its result line as a dict."""
    t0 = time.perf_counter() if t0 is None else t0
    log = log or (lambda s: print(s, file=sys.stderr, flush=True))
    from bench import peaks as peaks_mod
    from bench import trace_reduce

    spec = load_spec(root)
    entry, cell, config = load_cell(root, spec, name)
    devices = devices_for(int(entry["chips"]), require_tpu)
    kind = devices[0].device_kind
    known = kind in peaks_mod.PEAKS
    if require_tpu and not known:
        peaks_mod.peaks(kind)                      # raises
    ctx = Context(root, name, entry, cell, config, int(seed), devices,
                  peaks_mod.PEAKS.get(kind, peaks_mod.TPU_V5E))
    driver = load_module(root, "drivers", cell["driver"])

    state = driver.setup(ctx)
    setup_s = time.perf_counter() - t0
    length = min(seconds, cell.get("trace_seconds", seconds)) if trace \
        else seconds
    tdir = None
    if trace:
        import jax
        tdir = trace_dir or tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=opts)
    try:
        with count_compiles() as compiled, span("bench.window"):
            record = driver.window(state, length)
    finally:
        if trace:
            import jax
            jax.profiler.stop_trace()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    checks = driver.check(state, record)
    del state
    log(f"cell={name} seed={seed} window_s={record.window_s:.6f} "
        f"attempted={record.attempted} compiles_in_window={compiled['n']}")

    metrics: Dict[str, dict] = {}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    result: Dict[str, Any] = {}
    if trace:
        summary = trace_reduce.load(tdir)
        for i, d in enumerate(summary.devices):
            log(f"device {d.name}: busy_s={summary.busy_s(d)} "
                f"idle_share={summary.idle_share(d)}")
        for m in spec["per_layer"]:
            if not applies(m, name):
                continue
            value = load_module(root, "metrics", m["name"]).read(
                summary, record, ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if summary.devices:
            device.update(busy_s=summary.mean_busy_s(),
                          window_s=summary.window_s)
            result["breakdown"] = {"device_ops": summary.top_ops(10),
                                   "idle_gaps": summary.idle_gaps(10)}
        if trace_dir is None:
            shutil.rmtree(tdir, ignore_errors=True)
    else:
        for m in spec["end_to_end"]:
            if not applies(m, name):
                continue
            if m["name"] == "setup_s":
                value = setup_s
            elif m["name"] in record.e2e:
                value = record.e2e[m["name"]]
            else:
                raise BenchError(f"driver {cell['driver']!r} gives no "
                                 f"{m['name']!r}")
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    for c in checks:
        log(f"check {c.name}={c.value} limit={c.limit} "
            f"{'ok' if c.ok else 'FAILED'}")
    out = {"correct": all(c.ok for c in checks) and bool(checks),
           "attempted": record.attempted, "failed": record.failed,
           "metrics": metrics, "device": device}
    out.update(result)
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in checks}
    return out


def main(argv=None, t0: Optional[float] = None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the raw profiler trace here")
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed is a whole number >= 0")
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace), t0=t0, trace_dir=args.trace_dir)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    print(json.dumps(out), flush=True)
    return 0
