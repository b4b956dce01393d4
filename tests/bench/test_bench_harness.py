"""CPU rehearsal of whole runs: every driver at a tiny size through the
harness, the control and each planted fault coming out not correct, a cell
added by files alone, and `bench/run.py` refusing to run without a TPU."""

import json
import os
import subprocess
import sys

import pytest

from bench import faults, harness
from benchroot import ROOT, make_root


def run(root, cell, seed=2**33 + 7, seconds=0.2, trace=False):
    return harness.run_cell(cell, seed, seconds, trace, root=root,
                            require_tpu=False, log=lambda line: None)


ONE_CHIP = ["counters-zipf-1m", "counters-uniform-4k", "bfs-kron21-cas"]


@pytest.mark.parametrize("cell", ONE_CHIP)
def test_cell_runs_correct_with_its_metrics(tiny_root, cell):
    out = run(tiny_root, cell)
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] > 0
    spec = harness.load_spec(tiny_root)
    want = {m["name"] for m in spec["end_to_end"] if harness.applies(m, cell)}
    assert set(out["metrics"]) == want
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert list(out)[-1] == "checks"
    assert all(c["value"] == 0 and c["limit"] == 0
               for c in out["checks"].values())
    line = json.loads(json.dumps(out))
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}


def test_same_seed_same_inputs_other_seed_other_inputs(tiny_root):
    import numpy as np
    from bench import counters
    spec = harness.load_spec(tiny_root)
    entry, cell, config = harness.load_cell(tiny_root, spec,
                                            "counters-zipf-1m")

    def pool(seed):
        ctx = harness.Context(tiny_root, "counters-zipf-1m", entry, cell,
                              config, seed, [])
        idx, vals, _ = counters.make_pool(ctx, 256)
        return np.concatenate([np.asarray(a) for a in idx + vals])
    big = 2**31 + 12345
    assert np.array_equal(pool(big), pool(big))
    assert not np.array_equal(pool(big), pool(big + 2**32))


CASES = [("counters-zipf-1m", "control")]
CASES += [("counters-zipf-1m", f"fault:{k}")
          for k in faults.COUNTER_FAULTS[:3]]
CASES += [("counters-uniform-4k", "control")]
CASES += [("counters-uniform-4k", f"fault:{k}")
          for k in faults.COUNTER_FAULTS[:3]]
CASES += [("bfs-kron21-cas", "control")]
CASES += [("bfs-kron21-cas", f"fault:{k}") for k in faults.BFS_FAULTS]


@pytest.mark.parametrize("cell,mode", CASES)
def test_control_and_faults_are_not_correct(tiny_root, cell, mode):
    spec = harness.load_spec(tiny_root)
    _, c, _ = harness.load_cell(tiny_root, spec, cell)
    with faults.planted(c["driver"], mode):
        out = run(tiny_root, cell, seed=11)
    assert out["correct"] is False
    assert any(v["value"] > v["limit"] for v in out["checks"].values())


SHARDED = r"""
import os, sys, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path[:0] = [sys.argv[1], os.path.join(sys.argv[1], "src")]
from bench import faults, harness
root, out = sys.argv[2], {}
def go(mode):
    if mode == "program":
        r = harness.run_cell("counters4-zipf-256k", 2**32 + 3, 0.3, False,
                             root=root, require_tpu=False, log=lambda s: None)
    else:
        with faults.planted("counters_sharded", mode):
            r = harness.run_cell("counters4-zipf-256k", 5, 0.2, False,
                                 root=root, require_tpu=False,
                                 log=lambda s: None)
    out[mode] = [r["correct"], r["checks"], sorted(r["metrics"]),
                 r["device"]["count"]]
for mode in ["program", "control"] + [
        "fault:" + k for k in faults.COUNTER_FAULTS]:
    go(mode)
print(json.dumps(out))
"""


def test_sharded_cell_on_four_cpu_devices(tiny_root):
    """The four-chip cell on four fake CPU devices, in a child process (the
    device count is fixed when JAX starts)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", SHARDED, ROOT, tiny_root],
                          capture_output=True, text=True, env=env,
                          timeout=240)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    correct, checks, metrics, count = out.pop("program")
    assert correct is True and count == 4
    assert metrics == ["ops_per_s", "setup_s"]
    for mode, (correct, checks, _, _) in out.items():
        assert correct is False, mode


def test_a_cell_added_by_files_alone(tmp_path):
    """A new configuration, cell and per-layer metric: new files and new
    entries in BENCHMARK.json, no existing file edited."""
    root = make_root(tmp_path)
    bench = os.path.join(root, "bench")
    with open(os.path.join(bench, "configs", "ycsb_counters.json")) as f:
        config = json.load(f)
    config.update(name="tiny_counters", slots=512)
    with open(os.path.join(bench, "configs", "tiny_counters.json"), "w") as f:
        json.dump(config, f)
    with open(os.path.join(bench, "workloads",
                           "counters-uniform-4k.json")) as f:
        cell = json.load(f)
    cell.update(name="tiny-uniform", config="tiny_counters",
                ops_per_batch=32, pool_batches=3)
    with open(os.path.join(bench, "workloads", "tiny-uniform.json"),
              "w") as f:
        json.dump(cell, f)
    with open(os.path.join(bench, "metrics", "batches.count.py"), "w") as f:
        f.write("def read(trace, record, ctx):\n"
                "    return len(record.extra['batch_ids'])\n")
    before = {p: open(p).read() for p in _files(bench)
              if "tiny" not in p and "batches.count" not in p}
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "tiny_counters", "source": "a test",
                            "file": "bench/configs/tiny_counters.json",
                            "reduced": ["slots"], "why": "a test"})
    spec["workloads"].append({"name": "tiny-uniform",
                              "config": "tiny_counters",
                              "traffic": "tiny-uniform", "chips": 1,
                              "why": "a test"})
    spec["end_to_end"][0]["workloads"].append("tiny-uniform")
    spec["per_layer"].append({"name": "batches.count", "unit": "batches",
                              "better": "higher", "source": "host_clock",
                              "layer": "device", "moves": "ops_per_s",
                              "workloads": ["tiny-uniform"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    out = run(root, "tiny-uniform")
    assert out["correct"] is True
    assert set(out["metrics"]) == {"ops_per_s", "setup_s"}
    traced = run(root, "tiny-uniform", trace=True)
    assert traced["correct"] is True
    assert traced["metrics"]["batches.count"]["value"] > 0
    assert before == {p: open(p).read() for p in before}


def _files(top):
    for d, _, names in os.walk(top):
        for n in names:
            if n.endswith((".py", ".json")):
                yield os.path.join(d, n)


def test_run_py_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", "counters-zipf-1m", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=ROOT, timeout=120)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not proc.stdout.strip()


def test_run_py_refuses_in_a_checkout_without_the_program(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files."""
    import shutil
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "counters-zipf-1m",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=tmp_path, timeout=120)
    assert proc.returncode != 0
    assert not proc.stdout.strip()


def test_bfs_seeds_traverse_the_same_work(tiny_root):
    """Every seed relabels and reorders one graph and orders one key set:
    levels, reached vertices and Graph500's tuple count per key agree,
    while the parent arrays the seeds produce differ."""
    import numpy as np
    from bench.reference import bfs as ref
    driver = harness.load_module(tiny_root, "drivers", "bfs")
    spec = harness.load_spec(tiny_root)
    entry, cell, config = harness.load_cell(tiny_root, spec, "bfs-kron21-cas")
    n = 1 << int(config["scale"])
    work, parents = [], []
    for seed in (3, 2**33 + 3):
        ctx = harness.Context(tiny_root, "bfs-kron21-cas", entry, cell,
                              config, seed, [])
        (src, dst), roots, _ = driver._graph(ctx)
        src, dst = np.asarray(src), np.asarray(dst)
        rows = []
        for r in roots:
            p, levels = ref.bfs(src, dst, n, int(r))
            rows.append((levels, int((p >= 0).sum()),
                         ref.component_tuples(src[:src.size // 2], p)))
            parents.append(p)
        work.append(sorted(rows))
    assert work[0] == work[1]
    assert not np.array_equal(parents[0], parents[len(parents) // 2])
