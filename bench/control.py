#!/usr/bin/env python3
"""Read the numbers compared for ``correct`` on many seeds in one process.

    python bench/control.py --workload <cell> --seconds <s> --mode <mode> \
        --seeds <n> [<n> ...]

``--mode program`` runs the cell as `bench/run.py` does; ``control`` puts
the control of `bench/faults.py` in the program's place (the reference
with one stated guarantee broken, or the program's own path that breaks
it); ``fault:<kind>`` plants one of the faults there.  One JSON line per
seed: the seed, ``correct`` and each number with its limit.  The limits in
`bench/harness.py`'s checks are set from these readings; the benchmark's
own runs never run this.
"""

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0:1] = [ROOT, os.path.join(ROOT, "src")]


def main() -> int:
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--mode", default="program")
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--cpu", action="store_true",
                    help="allow a run without a TPU (rehearsal)")
    args = ap.parse_args()

    import jax
    from repro.launch.cache import setup_compile_cache
    setup_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from bench import faults, harness
    spec = harness.load_spec(ROOT)
    _, cell, _ = harness.load_cell(ROOT, spec, args.workload)
    for seed in args.seeds:
        t = time.perf_counter()
        if args.mode == "program":
            out = harness.run_cell(args.workload, seed, args.seconds, False,
                                   require_tpu=not args.cpu)
        else:
            with faults.planted(cell["driver"], args.mode):
                out = harness.run_cell(args.workload, seed, args.seconds,
                                       False, require_tpu=not args.cpu)
        print(json.dumps({"workload": args.workload, "mode": args.mode,
                          "seed": seed, "correct": out["correct"],
                          "attempted": out["attempted"],
                          "wall_s": time.perf_counter() - t,
                          "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
