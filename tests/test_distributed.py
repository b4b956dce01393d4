"""Distributed integration: real (not just compiled) steps on 8 fake devices.

Runs in a subprocess because XLA_FLAGS must be set before jax initializes —
the main pytest process keeps its single device (per the assignment, only
the dry-run may use placeholder devices)."""

import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
import numpy as np
from repro.configs import get_reduced, ShapeCell
from repro.launch import shardings as sh
from repro.launch.steps import abstract_train_state, make_train_step
from repro.launch.dryrun import input_specs
from repro.models.model import build_model
from repro.optim.adamw import AdamWConfig, init_state
from repro.data.pipeline import DataConfig, batch_kwargs_for, synthetic_batch
from repro.sharding import use_mesh

out = {}
from repro.sharding import make_mesh
mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
for arch in ["gemma_2b", "deepseek_v3_671b", "jamba_1_5_large_398b"]:
    cfg = get_reduced(arch)
    rules = sh.arch_rules(cfg, mesh, "train")
    model = build_model(cfg, attn_impl="chunked", remat_policy="full",
                        loss_chunk=64)
    opt_cfg = AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=10)
    dc = DataConfig(seq_len=32, global_batch=8, vocab_size=cfg.vocab_size)
    bkw = batch_kwargs_for(cfg)
    with use_mesh(mesh, rules):
        params = model.init(jax.random.PRNGKey(0))
        params_sh = sh.params_shardings(
            cfg, jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                              params), mesh, rules)
        params = jax.tree.map(lambda x, s: jax.device_put(x, s), params,
                              params_sh)
        opt = init_state(params, opt_cfg)
        step = jax.jit(make_train_step(model, opt_cfg), donate_argnums=(0, 1))
        losses = []
        for i in range(3):
            batch = synthetic_batch(dc, i, **bkw)
            params, opt, metrics = step(params, opt, batch)
            losses.append(float(metrics["loss"]))
        out[arch] = losses

# single-device equivalence: sharded loss == unsharded loss (same seed)
cfg = get_reduced("gemma_2b")
model = build_model(cfg, attn_impl="chunked", remat_policy="full",
                    loss_chunk=64)
dc = DataConfig(seq_len=32, global_batch=8, vocab_size=cfg.vocab_size)
params = model.init(jax.random.PRNGKey(0))
batch = synthetic_batch(dc, 0)
loss_local = float(model.loss(params, batch))
rules = sh.arch_rules(cfg, mesh, "train")
with use_mesh(mesh, rules):
    loss_sharded = float(jax.jit(model.loss)(params, batch))
out["equivalence"] = [loss_local, loss_sharded]
print("RESULT:" + json.dumps(out))
"""


@pytest.fixture(scope="module")
def dist_result():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900,
                          cwd=os.path.dirname(os.path.dirname(
                              os.path.abspath(__file__))))
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines()
            if l.startswith("RESULT:")][0]
    return json.loads(line[len("RESULT:"):])


def test_sharded_train_steps_finite(dist_result):
    import math
    for arch in ("gemma_2b", "deepseek_v3_671b", "jamba_1_5_large_398b"):
        losses = dist_result[arch]
        assert len(losses) == 3
        assert all(math.isfinite(x) for x in losses), (arch, losses)


def test_sharded_matches_local_loss(dist_result):
    local, sharded = dist_result["equivalence"]
    assert abs(local - sharded) / abs(local) < 5e-2, (local, sharded)
