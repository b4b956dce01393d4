#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The last line of standard output is the result as one JSON object; the
numbers compared for ``correct`` are the last lines of standard error.
Exits non-zero, printing no result, when JAX sees no TPU, fewer chips than
the cell needs, or a chip missing from `bench/peaks.py`.
"""

import time

T0 = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0:1] = [ROOT, os.path.join(ROOT, "src")]


def main() -> int:
    from repro.launch.cache import setup_compile_cache

    import jax
    setup_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from bench.harness import main as harness_main
    return harness_main(t0=T0)


if __name__ == "__main__":
    sys.exit(main())
