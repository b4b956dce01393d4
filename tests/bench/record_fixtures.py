#!/usr/bin/env python3
"""Record the scoped traces the benchmark's tests read, on one TPU chip.

    python tests/bench/record_fixtures.py OUTDIR

Runs ``counters-uniform-4k`` and ``bfs-kron21-cas``, cut to the sizes of
`benchroot.TINY_CELLS`, through the harness with the profiler on (the
staged counter cell read by every per-layer metric of
``counters-zipf-1m``), and
writes the two traces as ``OUTDIR/counters_scoped_1chip.xplane.pb`` and
``OUTDIR/bfs_scoped_1chip.xplane.pb``, with each run's result line beside
them (``*.json``).  The program's spans and ``jax.named_scope`` names are
in them: `bench/scopes.py` and the per-layer readers are tested on them.
The programs are compiled with no source lines in their metadata, so the
traces name no path of the checkout they were recorded in.
"""

import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[0:0] = [ROOT, os.path.join(ROOT, "src"), HERE]

CELLS = {"counters-uniform-4k": "counters_scoped_1chip",
         "bfs-kron21-cas": "bfs_scoped_1chip"}
SEED = 2**33 + 7
SECONDS = 0.02


def main(out: str) -> int:
    import jax
    from bench import harness, trace_reduce
    from benchroot import make_root
    from repro.launch.cache import setup_compile_cache

    setup_compile_cache()
    jax.config.update("jax_traceback_in_locations_limit", 0)

    os.makedirs(out, exist_ok=True)
    root = make_root(tempfile.mkdtemp(prefix="bench-root-"))
    spec_path = os.path.join(root, "BENCHMARK.json")
    with open(spec_path) as f:
        spec = json.load(f)
    for m in spec["per_layer"]:
        if "counters-zipf-1m" in m.get("workloads", []):
            m["workloads"].append("counters-uniform-4k")
    with open(spec_path, "w") as f:
        json.dump(spec, f)
    for cell, name in CELLS.items():
        tdir = tempfile.mkdtemp(prefix="bench-trace-")
        line = harness.run_cell(cell, SEED, SECONDS, True, root=root,
                                trace_dir=tdir)
        shutil.copy(trace_reduce.find_xplane(tdir),
                    os.path.join(out, f"{name}.xplane.pb"))
        with open(os.path.join(out, f"{name}.json"), "w") as f:
            json.dump(line, f, indent=1)
        print(cell, json.dumps(line["metrics"]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
