"""A copy of the benchmark's files with every cell cut to a CPU size."""

import json
import os
import shutil

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: every cell cut to a size the CPU runs in well under a second
TINY_CELLS = {
    "counters-zipf-1m": {"ops_per_batch": 256},
    "counters-uniform-4k": {"ops_per_batch": 64, "pool_batches": 5,
                            "checked_batches": 40},
    "bfs-kron21-cas": {"roots": 8},
    "counters4-zipf-256k": {"ops_per_chip": 128},
}
TINY_CONFIGS = {
    "ycsb_counters": {"slots": 1024},
    "graph500_kron": {"scale": 8},
    "ycsb_counters_4chip": {"slots": 4096},
}

#: Cells the drivers run that are not in BENCHMARK.json yet (PERF.md, Open
#: questions): uniform keys on the eager path, and the table sharded over a
#: 2x2 mesh.  Each is its workload file, its configuration file if it has
#: its own, and its entry.
STAGED = {
    "counters-uniform-4k": (
        {"name": "counters-uniform-4k", "config": "ycsb_counters",
         "driver": "counters_eager", "ops_per_batch": 4096,
         "pool_batches": 256, "keys": "uniform", "value_max": 32768,
         "backend": "auto", "trace_seconds": 2, "checked_batches": 256},
        None, 1),
    "counters4-zipf-256k": (
        {"name": "counters4-zipf-256k", "config": "ycsb_counters_4chip",
         "driver": "counters_sharded", "ops_per_chip": 262144,
         "pool_batches": 8, "keys": "zipf", "value_max": 32768,
         "strategy": "auto", "trace_seconds": 3, "checked_batches": 6},
        {"name": "ycsb_counters_4chip", "slots": 1 << 28, "dtype": "int32",
         "op": "faa", "zipf_constant": 0.99, "initial_min": 0,
         "initial_max": (1 << 20) - 1, "chips": 4, "mesh": [2, 2],
         "mesh_axes": ["pod", "dev"]},
        4),
}


def stage(path, name: str) -> None:
    """Add the staged cell ``name`` to the copy of the benchmark at ``path``
    by files and entries alone."""
    cell, config, chips = STAGED[name]
    bench = os.path.join(path, "bench")
    _write(os.path.join(bench, "workloads", f"{name}.json"), cell)
    spec_path = os.path.join(path, "BENCHMARK.json")
    with open(spec_path) as f:
        spec = json.load(f)
    if config is not None:
        rel = f"bench/configs/{config['name']}.json"
        _write(os.path.join(path, rel), config)
        spec["configs"].append({"name": config["name"], "source": "a test",
                                "file": rel, "reduced": [], "why": "a test"})
    spec["workloads"].append({"name": name, "config": cell["config"],
                              "traffic": name, "chips": chips,
                              "why": "a test"})
    for m in spec["end_to_end"]:
        if m["name"] == "ops_per_s":
            m["workloads"].append(name)
    _write(spec_path, spec)


def _write(path, data) -> None:
    with open(path, "w") as f:
        json.dump(data, f)


def make_root(path, cells=TINY_CELLS, configs=TINY_CONFIGS) -> str:
    """A copy of the benchmark's files under ``path``, the staged cells
    added, with each cell's and configuration's sizes overridden."""
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(path, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), path)
    for name in STAGED:
        stage(path, name)
    for sub, table in (("workloads", cells), ("configs", configs)):
        for name, over in table.items():
            p = os.path.join(path, "bench", sub, f"{name}.json")
            with open(p) as f:
                data = json.load(f)
            data.update(over)
            _write(p, data)
    return str(path)
