#!/usr/bin/env python3
"""Record the scoped trace of the sharded counter cell, on a 2x2 TPU host.

    python tests/bench/record_sharded_fixture.py OUTDIR

Runs ``counters4-zipf-256k`` from the committed benchmark files, cut to
``ops_per_chip`` 128 and ``slots`` 4096 (`shardedroot.TINY`),
for a few steps with the profiler on, and writes the trace as
``OUTDIR/counters4_scoped_2x2.xplane.pb`` with, beside it
(``counters4_scoped_2x2.json``), the window's record and what each
per-layer reader of the cell read from it.  The programs are compiled with
no source lines in their metadata, so the trace names no path of the
checkout it was recorded in.
"""

import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[0:0] = [ROOT, os.path.join(ROOT, "src"), HERE]

NAME = "counters4_scoped_2x2"
SEED = 2**33 + 7
SECONDS = 0.02


def main(out: str) -> int:
    import jax
    from bench import harness, peaks, scopes, trace_reduce
    from repro.launch.cache import setup_compile_cache
    from shardedroot import CELL, make_u32_root

    setup_compile_cache()
    jax.config.update("jax_traceback_in_locations_limit", 0)
    os.makedirs(out, exist_ok=True)
    root = make_u32_root(tempfile.mkdtemp(prefix="bench-root-"))
    spec = harness.load_spec(root)
    entry, cell, config = harness.load_cell(root, spec, CELL)
    devices = harness.devices_for(int(entry["chips"]), True)
    ctx = harness.Context(root, CELL, entry, cell, config, SEED, devices,
                          peaks.peaks(devices[0].device_kind))
    driver = harness.load_module(root, "drivers", cell["driver"])
    state = driver.setup(ctx)
    tdir = tempfile.mkdtemp(prefix="bench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tdir, profiler_options=opts)
    try:
        with harness.span("bench.window"):
            record = driver.window(state, SECONDS)
    finally:
        jax.profiler.stop_trace()
    checks = driver.check(state, record)
    trace = scopes.load_trace(tdir)
    readings = {m["name"]: harness.load_module(root, "metrics", m["name"])
                .read(trace, record, ctx)
                for m in spec["per_layer"] if harness.applies(m, CELL)}
    shutil.copy(trace_reduce.find_xplane(tdir),
                os.path.join(out, f"{NAME}.xplane.pb"))
    with open(os.path.join(out, f"{NAME}.json"), "w") as f:
        json.dump({"extra": record.extra, "readings": readings,
                   "checks": {c.name: c.value for c in checks}}, f, indent=1)
    print(CELL, json.dumps(readings), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
