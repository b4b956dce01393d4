"""Elastic resize: checkpoint written under one mesh restores under another.

Runs in a subprocess (needs 8 fake devices before jax init)."""

import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, tempfile
import jax, numpy as np
from repro.configs import get_reduced
from repro.launch import shardings as sh
from repro.models.model import build_model
from repro.optim.adamw import AdamWConfig, init_state
from repro.checkpoint import ckpt as ckpt_lib
from repro.runtime.elastic import reshard_restore, survivors_mesh
from repro.sharding import use_mesh

import jax.numpy as jnp
from repro import atomics

cfg = get_reduced("gemma_2b")
model = build_model(cfg, attn_impl="ref", remat_policy="none", loss_chunk=64)
opt_cfg = AdamWConfig()

# write under an 8-chip mesh (data=4, model=2)
from repro.sharding import make_mesh
mesh_a = make_mesh((4, 2), ("data", "model"))
rules_a = sh.arch_rules(cfg, mesh_a, "train")
with use_mesh(mesh_a, rules_a):
    params = model.init(jax.random.PRNGKey(0))
    opt = init_state(params, opt_cfg)
    counters = atomics.make_table(64, jnp.int32, fill=9)  # live RMW state
d = tempfile.mkdtemp()
ckpt_lib.save(d, 5, {"params": params, "opt": opt, "counters": counters})

# restore under a shrunken mesh (lost half the data shards): 2x2
mesh_b = survivors_mesh({"data": 4, "model": 2}, lost_data_shards=2)
like = {"params": params, "opt": opt, "counters": counters}
state, _ = reshard_restore(d, 5, like, cfg, mesh_b)

# bitwise identical content, new placement — AtomicTable included
# (its owner-major layout re-derived under mesh_b, not the writer's mesh)
ok = True
for a, b in zip(jax.tree.leaves(like), jax.tree.leaves(state)):
    if not np.array_equal(np.asarray(a, np.float32),
                          np.asarray(b, np.float32)):
        ok = False
tbl = state["counters"]
ok &= isinstance(tbl, atomics.AtomicTable)
ok &= tbl.data.sharding.mesh.shape.get("data", 0) == 2
# and the restored params still produce the same loss on the new mesh
from repro.data.pipeline import DataConfig, synthetic_batch
dc = DataConfig(seq_len=16, global_batch=4, vocab_size=cfg.vocab_size)
batch = synthetic_batch(dc, 0)
loss_a = float(model.loss(params, batch))
rules_b = sh.arch_rules(cfg, mesh_b, "train")
with use_mesh(mesh_b, rules_b):
    loss_b = float(jax.jit(model.loss)(state["params"], batch))
print("RESULT:" + json.dumps({"bitwise": ok, "loss_a": loss_a,
                              "loss_b": loss_b}))
"""


def test_elastic_reshard_roundtrip():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600,
                          cwd=os.path.dirname(os.path.dirname(
                              os.path.abspath(__file__))))
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines()
            if l.startswith("RESULT:")][0]
    res = json.loads(line[len("RESULT:"):])
    assert res["bitwise"]
    assert abs(res["loss_a"] - res["loss_b"]) / abs(res["loss_a"]) < 1e-3
