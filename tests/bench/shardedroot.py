"""A copy of the committed benchmark files with the sharded counter cell
cut to a CPU size.

`benchroot.make_root` stages its own ``counters4-zipf-256k`` (the int32
driver at 2^28 slots) over the committed one; this copy keeps the committed
cell and configuration, with only their sizes overridden."""

import json
import os
import shutil

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

CELL = "counters4-zipf-256k"
#: the cell and its configuration at a size four CPU devices run at once
TINY = {("workloads", CELL): {"ops_per_chip": 128},
        ("configs", "ycsb_counters_4chip"): {"slots": 4096}}


def make_u32_root(path) -> str:
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(path, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), path)
    for (sub, name), over in TINY.items():
        p = os.path.join(path, "bench", sub, f"{name}.json")
        with open(p) as f:
            data = json.load(f)
        data.update(over)
        with open(p, "w") as f:
            json.dump(data, f)
    return str(path)
