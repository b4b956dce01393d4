"""The ``jax.named_scope`` names the programs carry into the device trace:
each scope is on the ``op_name`` of the HLO the program compiles to."""

import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.core import bfs, rmw, rmw_engine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def scopes_of(compiled, prefixes=("rmw.", "bfs.", "exchange.")):
    """The scope names on the ``op_name`` of every instruction."""
    names = re.findall(r'op_name="([^"]*)"', compiled.as_text())
    return {part for name in names for part in name.split("/")
            if part.startswith(prefixes)}


TABLE = jnp.zeros((256,), jnp.int32)
IDX = (jnp.arange(64, dtype=jnp.int32) * 7) % 256
VALS = jnp.ones((64,), jnp.int32)
SORT_SCOPES = {"rmw.sort", "rmw.gather", "rmw.scan", "rmw.scatter",
               "rmw.unsort"}


@pytest.mark.parametrize("op", ["faa", "min", "max", "swp"])
def test_rmw_combining_scopes(op):
    compiled = rmw.rmw_combining.lower(TABLE, IDX, VALS, op).compile()
    assert scopes_of(compiled) == SORT_SCOPES


def test_cas_uniform_scopes():
    compiled = rmw.rmw_combining.lower(TABLE, IDX, VALS, "cas",
                                       jnp.int32(0)).compile()
    assert scopes_of(compiled) == SORT_SCOPES


@pytest.mark.parametrize("op", ["faa", "min", "swp", "cas"])
def test_tables_only_is_one_scatter_scope(op):
    exp = jnp.int32(0) if op == "cas" else None
    compiled = jax.jit(rmw_engine._tables_only, static_argnums=(3,)).lower(
        TABLE, IDX, VALS, op, exp).compile()
    assert scopes_of(compiled) == {"rmw.scatter"}


def test_onehot_table_pass_is_scoped():
    compiled = rmw_engine.rmw_onehot.lower(TABLE, IDX, VALS,
                                           "faa").compile()
    assert "rmw.scatter" in scopes_of(compiled)


@pytest.mark.parametrize("op", ["cas", "swp", "faa"])
def test_bfs_level_scopes(op):
    src = jnp.arange(512, dtype=jnp.int32) % 64
    dst = (jnp.arange(512, dtype=jnp.int32) * 5) % 64
    compiled = bfs._bfs_run.lower(src, dst, jnp.int32(0), 64, op).compile()
    assert {"bfs.expand", "bfs.claim", "bfs.frontier"} <= scopes_of(compiled)
    # every engine scope in the loop lies inside the claim
    names = re.findall(r'op_name="([^"]*)"', compiled.as_text())
    assert all("bfs.claim/" in n for n in names if "/rmw." in n)


_SHARDED = r"""
import json, re
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro import atomics
from repro.sharding import make_mesh, shard_map_compat

mesh = make_mesh((2, 2), ("pod", "dev"))
spec = P(("pod", "dev"))
out = {}
for strategy in ("oneshot", "hierarchical", "naive"):
    def body(t, i, v):
        tbl = atomics.AtomicTable(t, axis=("pod", "dev"))
        res = atomics.execute(tbl, atomics.Faa(i, v), strategy=strategy)
        return res.table.data, res.fetched
    fn = jax.jit(shard_map_compat(body, mesh, (spec, spec, spec),
                                  (spec, spec)))
    t = jnp.zeros((64,), jnp.int32)
    i = (jnp.arange(32, dtype=jnp.int32) * 5) % 64
    text = fn.lower(t, i, jnp.ones((32,), jnp.int32)).compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', text)
    out[strategy] = sorted({p for n in names for p in n.split("/")
                            if p.startswith("exchange.")})
    a2a = [line for line in text.splitlines()
           if re.search(r" all-to-all\(", line)]
    out[strategy + ".collectives_scoped"] = bool(a2a) and all(
        "exchange.send/" in line or "exchange.return/" in line
        for line in a2a)
print("RESULT:" + json.dumps(out))
"""


def test_sharded_exchange_scopes_on_four_devices():
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", _SHARDED], env=env,
                          capture_output=True, text=True, timeout=600,
                          cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT:")][0]
    out = json.loads(line[len("RESULT:"):])
    every = ["exchange.precombine", "exchange.resolve", "exchange.return",
             "exchange.send"]
    for strategy in ("oneshot", "hierarchical", "naive"):
        assert out[strategy] == every, strategy
        assert out[strategy + ".collectives_scoped"] is True, strategy


_CACHED = r"""
import os, sys
import numpy as np
import jax, jax.numpy as jnp
from repro.launch.cache import setup_compile_cache
setup_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
hits = []
jax.monitoring.register_event_listener(
    lambda e, **k: hits.append(e) if e.endswith("cache_hits") else None)

@jax.jit
def f(x):
    with jax.named_scope(sys.argv[1]):
        return jnp.sort(x) + 1

f(np.arange(8)).block_until_ready()     # f is the one program compiled
print("HITS:%d" % len(hits))
"""


def test_compile_cache_keys_on_the_scope_names(tmp_path):
    """A program cached under other ``jax.named_scope`` names is compiled
    anew, so the trace never shows the old names; the same source hits."""
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    (tmp_path / "prog.py").write_text(_CACHED)

    def hits(scope):
        proc = subprocess.run(
            [sys.executable, str(tmp_path / "prog.py"), scope],
            env=env, capture_output=True, text=True, timeout=300, cwd=ROOT)
        assert proc.returncode == 0, proc.stderr[-2000:]
        return int(proc.stdout.split("HITS:")[1])

    assert hits("phase.a") == 0
    assert hits("phase.b") == 0
    assert hits("phase.a") > 0
    assert hits("phase.b") > 0
