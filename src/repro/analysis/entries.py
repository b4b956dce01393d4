"""Registered entry points for the lint sweep.

Each entry is a zero-arg callable returning a findings list; the CLI
(`python -m repro.analysis.lint`) and the ``atomics_lint`` pytest fixture
sweep all of them.  Entries build their functions-under-analysis from
*reduced* configs with `jax.ShapeDtypeStruct` stand-ins wherever shapes
suffice — the sweep traces jaxprs but never runs a model, so it stays
fast enough for CI's lint lane.

Register new atomics-touching code paths here: an entry that exists is an
entry the linter guards.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro import analysis
from repro.analysis.findings import Finding


def _sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


# ---------------------------------------------------------------------------
# MoE dispatch (models/moe.py) — the densest atomics consumer in the repo
# ---------------------------------------------------------------------------

def check_moe_local() -> List[Finding]:
    from repro.configs import get_reduced
    from repro.models.moe import moe_ffn, moe_init

    out: List[Finding] = []
    base = get_reduced("dbrx_132b")
    params = jax.eval_shape(
        lambda: moe_init(jax.random.PRNGKey(0), base, jnp.float32))
    x = _sds((2, 8, base.d_model))
    for policy in ("cas_keep_top_gate", "swp_drop_newest"):
        cfg = dataclasses.replace(
            base, moe=dataclasses.replace(base.moe, overflow_policy=policy))
        out += analysis.check(lambda p, xx: moe_ffn(p, xx, cfg), params, x,
                              entry=f"moe.local[{policy}]")
    return out


# ---------------------------------------------------------------------------
# BFS (core/bfs.py) — bounded while+CAS loops that must NOT trip A003
# ---------------------------------------------------------------------------

def check_bfs_local() -> List[Finding]:
    from repro.core.bfs import _bfs_run

    out: List[Finding] = []
    n = 8
    src = np.array([0, 0, 1, 2, 4, 5], np.int32)
    dst = np.array([1, 2, 3, 3, 5, 6], np.int32)
    root = np.int32(0)
    for op in ("cas", "swp", "faa"):
        out += analysis.check(
            partial(_bfs_run, n=n, op=op, max_levels=8), src, dst, root,
            entry=f"bfs.local[{op}]")
    return out


# ---------------------------------------------------------------------------
# training (launch/train.py path) — donation hygiene end to end
# ---------------------------------------------------------------------------

def _reduced_model():
    from repro.configs import get_reduced
    from repro.models.model import build_model

    cfg = get_reduced("gemma_2b")
    return cfg, build_model(cfg, attn_impl="ref")


def check_train_step() -> List[Finding]:
    from repro.data.pipeline import DataConfig, synthetic_batch
    from repro.launch.steps import abstract_train_state, make_train_step
    from repro.optim.adamw import AdamWConfig

    cfg, model = _reduced_model()
    opt_cfg = AdamWConfig()
    params, opt = abstract_train_state(model, opt_cfg)
    batch = synthetic_batch(
        DataConfig(seq_len=8, global_batch=2, vocab_size=cfg.vocab_size), 0)
    step = jax.jit(make_train_step(model, opt_cfg), donate_argnums=(0, 1))
    return analysis.check(step, params, opt, batch, entry="train.step")


def check_train_recovery() -> List[Finding]:
    from repro.launch.steps import make_train_step
    from repro.optim.adamw import AdamWConfig
    from repro.runtime.fault_tolerance import declare_donation

    _, model = _reduced_model()
    step = declare_donation(
        jax.jit(make_train_step(model, AdamWConfig()),
                donate_argnums=(0, 1)), (0, 1))
    # the trainer passes a zero-arg factory (launch/train.py fresh_state);
    # this entry pins that contract so a regression to a captured value —
    # the PR-6 bug — fails lint before it fails a chaos run
    return analysis.check_recovery(step, lambda: None,
                                   entry="train.recovery")


# ---------------------------------------------------------------------------
# serving (launch/serve.py path) — KV-cache update hygiene
# ---------------------------------------------------------------------------

def check_serve_prefill() -> List[Finding]:
    _, model = _reduced_model()
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    batch = {"tokens": _sds((1, 8), jnp.int32)}
    return analysis.check(lambda p, b: model.prefill(p, b, 16), params,
                          batch, entry="serve.prefill")


def check_serve_decode() -> List[Finding]:
    _, model = _reduced_model()
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    batch = {"tokens": _sds((1, 8), jnp.int32)}
    cache, _ = jax.eval_shape(lambda p, b: model.prefill(p, b, 16), params,
                              batch)
    tok = {"tokens": _sds((1, 1), jnp.int32)}
    return analysis.check(lambda p, c, b: model.decode_step(p, c, b),
                          params, cache, tok, entry="serve.decode")


# ---------------------------------------------------------------------------
# self-tuning (repro.tuning) — controller-wrapped steps stay lint-clean
# ---------------------------------------------------------------------------

def check_tuning_train_step() -> List[Finding]:
    """`SpecController.wrap_step` around the donating train step: the
    wrapper must preserve the donation contract (rule A004) and add no
    atomics hazards of its own — an unstarted controller's step() is a
    no-op, so the sweep needs no live stream."""
    from repro.data.pipeline import DataConfig, synthetic_batch
    from repro.launch.steps import abstract_train_state, make_train_step
    from repro.optim.adamw import AdamWConfig
    from repro.runtime.fault_tolerance import declare_donation
    from repro.tuning import SpecController

    cfg, model = _reduced_model()
    opt_cfg = AdamWConfig()
    params, opt = abstract_train_state(model, opt_cfg)
    batch = synthetic_batch(
        DataConfig(seq_len=8, global_batch=2, vocab_size=cfg.vocab_size), 0)
    ctrl = SpecController()
    step = ctrl.wrap_step(declare_donation(
        jax.jit(make_train_step(model, opt_cfg), donate_argnums=(0, 1)),
        (0, 1)))
    out = analysis.check(step, params, opt, batch,
                         entry="tuning.train_step")
    out += analysis.check_recovery(step, lambda: None,
                                   entry="tuning.train_step")
    return out


def check_tuning_serve_decode() -> List[Finding]:
    from repro.tuning import SpecController

    _, model = _reduced_model()
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    batch = {"tokens": _sds((1, 8), jnp.int32)}
    cache, _ = jax.eval_shape(lambda p, b: model.prefill(p, b, 16), params,
                              batch)
    tok = {"tokens": _sds((1, 1), jnp.int32)}
    ctrl = SpecController()
    step = ctrl.wrap_step(lambda p, c, b: model.decode_step(p, c, b))
    return analysis.check(step, params, cache, tok,
                          entry="tuning.serve_decode")


# ---------------------------------------------------------------------------
# sharded execute (examples/sharded_atomics.py pattern) — A005 coverage
# ---------------------------------------------------------------------------

def check_examples_sharded() -> List[Finding]:
    from jax.sharding import PartitionSpec as P

    from repro import atomics
    from repro.sharding import make_mesh, shard_map_compat

    mesh = make_mesh((1,), ("dev",))
    spec = P("dev")

    def fn(t, i, v):
        tbl = atomics.AtomicTable(t, axis="dev")
        res = atomics.execute(tbl, atomics.Faa(i[0], v[0]))
        return res.table.data, res.fetched[None]

    wrapped = shard_map_compat(fn, mesh, (spec, spec, spec), (spec, spec))
    return analysis.check(wrapped, _sds((8,), jnp.int32),
                          _sds((1, 4), jnp.int32), _sds((1, 4), jnp.int32),
                          entry="examples.sharded_atomics")


#: name -> zero-arg callable returning findings; ``lint.sweep`` iterates
#: this in order
ENTRY_POINTS: Dict[str, Callable[[], List[Finding]]] = {
    "moe.local": check_moe_local,
    "bfs.local": check_bfs_local,
    "train.step": check_train_step,
    "train.recovery": check_train_recovery,
    "serve.prefill": check_serve_prefill,
    "serve.decode": check_serve_decode,
    "tuning.train_step": check_tuning_train_step,
    "tuning.serve_decode": check_tuning_serve_decode,
    "examples.sharded_atomics": check_examples_sharded,
}
