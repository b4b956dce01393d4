"""Training driver: --arch/--shape selectable, fault-tolerant, resumable.

On this container it runs real steps single-device at reduced scale
(examples/train_100m.py drives it); on a TPU fleet the same entry point runs
under the production mesh (launch/mesh.py) — the step function, checkpoint
layout, and data pipeline are identical.
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro import telemetry
from repro.checkpoint import ckpt as ckpt_lib
from repro.checkpoint.ckpt import AsyncCheckpointer
from repro.configs import get_config, get_reduced
from repro.data.pipeline import DataConfig, batch_kwargs_for, synthetic_batch
from repro.launch import shardings as sh
from repro.launch.cache import setup_compile_cache
from repro.launch.steps import make_train_step
from repro.models.model import build_model
from repro.optim.adamw import AdamWConfig, init_state
from repro.runtime.chaos import FaultPlan
from repro.runtime.fault_tolerance import (FaultConfig, StragglerMonitor,
                                           declare_donation,
                                           run_with_recovery)
from repro.sharding import use_mesh

log = logging.getLogger("repro.train")


def train(arch: str, *, steps: int = 100, seq_len: int = 256,
          global_batch: int = 8, reduced: bool = True,
          ckpt_dir: Optional[str] = None, checkpoint_every: int = 50,
          mesh=None, rules: Optional[Dict] = None, lr: float = 3e-4,
          microbatches: int = 1, log_every: int = 10,
          failure_injector=None, seed: int = 0,
          remat_policy: str = "none",
          chaos: Optional[FaultPlan] = None,
          tuning=None) -> Dict[str, Any]:
    """Returns final metrics dict.  Deterministic given (arch, seed, steps)
    — including under an injected fault schedule (`chaos`, or the
    ``REPRO_CHAOS`` env hook when None): recovery restores the latest
    *valid* checkpoint and replays, so the final state is bit-equal to a
    fault-free run.

    ``tuning``: a started-or-not `repro.tuning.SpecController`, True for a
    default one, or None to consult the ``REPRO_TUNING`` env hook.  The
    controller is stepped once per training step (guarded live-spec
    updates from the run's own drift telemetry) and stopped on exit; the
    spec steers dispatch selection only, so tuned metrics/losses stay
    bit-equal to untuned runs."""
    cfg = get_reduced(arch) if reduced else get_config(arch)
    model = build_model(cfg, attn_impl="chunked", remat_policy=remat_policy,
                        loss_chunk=2048)
    opt_cfg = AdamWConfig(lr=lr, warmup_steps=min(20, steps // 5 + 1),
                          total_steps=steps)
    data_cfg = DataConfig(seq_len=seq_len, global_batch=global_batch,
                          vocab_size=cfg.vocab_size, seed=seed)
    bkw = batch_kwargs_for(cfg)

    params = model.init(jax.random.PRNGKey(seed))
    opt_state = init_state(params, opt_cfg)
    step_fn = jax.jit(make_train_step(model, opt_cfg,
                                      microbatches=microbatches),
                      donate_argnums=(0, 1))

    # step_fn DONATES its inputs, so the initial buffers are consumed by
    # step 0 — a post-failure scratch restart must rebuild state, not
    # reuse them.  First call hands out the arrays built above; later
    # calls re-init deterministically from the same seed.
    _first_init = [(params, opt_state)]

    def fresh_state():
        if _first_init:
            return _first_init.pop()
        p = model.init(jax.random.PRNGKey(seed))
        return p, init_state(p, opt_cfg)

    saver = AsyncCheckpointer(ckpt_dir, keep=3) if ckpt_dir else None
    monitor = StragglerMonitor(n_hosts=1, cfg=FaultConfig())
    history = []

    def one_step(step: int, state):
        params, opt_state = state
        batch = synthetic_batch(data_cfg, step, **bkw)
        t0 = time.time()
        # the span is the per-step profiler hook: wall_s lands in the event
        # stream, and under enable(annotate=True) the step also shows up as
        # a named range in a jax.profiler trace
        with telemetry.annotation(f"train.step/{step}"), \
                telemetry.span("train.step", step=step, arch=arch):
            params, opt_state, metrics = step_fn(params, opt_state, batch)
            metrics = {k: float(v) for k, v in metrics.items()}
        dt = time.time() - t0
        monitor.record(0, dt)
        if step % log_every == 0 or step == steps - 1:
            log.info("step %4d loss=%.4f lr=%.2e gnorm=%.3f %.2fs",
                     step, metrics["loss"], metrics["lr"],
                     metrics["grad_norm"], dt)
            history.append({"step": step, **metrics, "sec": dt})
        return params, opt_state

    # donation metadata travels with the callable: the state argument's
    # buffers are consumed each call (the inner jit donates params+opt), so
    # recovery and the static linter (rule A004) can verify that the
    # init_state handed over below is a factory, not a captured value
    one_step = declare_donation(one_step, (1,))

    controller = _resolve_tuning(tuning)
    if controller is not None:
        controller.start()
        # wrap_step preserves the donation metadata declared above
        one_step = controller.wrap_step(one_step)

    def save_fn(step: int, state):
        if saver is not None:
            saver.save_async(step, {"params": state[0], "opt": state[1]},
                             extra={"arch": arch, "seed": seed})

    def restore_fn():
        if not ckpt_dir:
            return None
        # if the saver's background thread died mid-write, surface it here
        # (and drop the torn step on the floor: restore_latest_valid walks
        # straight past it to the newest checkpoint that checksums clean)
        if saver is not None:
            try:
                saver.wait()
            except Exception as e:  # noqa: BLE001 — recovery handles it
                log.warning("async save failed (%s); restoring the newest "
                            "valid step instead", e)
        like = {"params": params, "opt": opt_state}
        got = ckpt_lib.restore_latest_valid(ckpt_dir, like)
        if got is None:
            return None
        last, tree, _extra = got
        return last, (tree["params"], tree["opt"])

    fault_cfg = FaultConfig(checkpoint_every=checkpoint_every)
    ctx = use_mesh(mesh, rules or {}) if mesh is not None else _null_ctx()
    # elastic adoption: every restored state re-lands its live AtomicTables
    # on the CURRENT mesh (layout re-derivation, not history replay); a
    # table-free state tree passes through untouched
    reshard_fn = None
    if mesh is not None:
        from repro.runtime.elastic import reshard_tables
        reshard_fn = lambda s: reshard_tables(s, mesh)  # noqa: E731
    try:
        with ctx:
            result = run_with_recovery(one_step, fresh_state, steps,
                                       fault_cfg, save_fn, restore_fn,
                                       failure_injector=failure_injector,
                                       reshard_fn=reshard_fn, chaos=chaos)
    finally:
        if controller is not None:
            controller.stop()        # detach, clear live spec, persist
    if saver is not None:
        saver.wait()
    out = {"history": history, "steps_done": result.steps_done,
           "failures": result.failures,
           "backoff_total_s": result.backoff_total_s,
           "final_loss": history[-1]["loss"] if history else None}
    if controller is not None:
        out["tuning"] = controller.stats()
    return out


def _resolve_tuning(tuning):
    """None → the REPRO_TUNING env hook; True → a default controller;
    a SpecController instance passes through."""
    if tuning is None:
        from repro.tuning import from_env
        return from_env()
    if tuning is True:
        from repro.tuning import SpecController
        return SpecController()
    return tuning


class _null_ctx:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


def main() -> None:
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")
    setup_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--full", action="store_true",
                    help="full-size config (TPU fleet); default reduced")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--chaos", default=None, metavar="SPEC",
                    help="fault-injection spec, e.g. 'seed=7,step=0.05,"
                         "ckpt_save=0.1@2' (same syntax as REPRO_CHAOS)")
    ap.add_argument("--telemetry", default=None, metavar="SINK",
                    help="'ring' or a JSONL path: enable the repro.telemetry "
                         "event stream (same as REPRO_TELEMETRY); render a "
                         "capture with `python -m repro.telemetry.report`")
    ap.add_argument("--tuning", nargs="?", const="on", default=None,
                    metavar="STATE",
                    help="run under a repro.tuning.SpecController (guarded "
                         "live HardwareSpec updates from the run's own "
                         "drift telemetry); optional value = state file the "
                         "tuned spec persists/restores through (same as "
                         "REPRO_TUNING)")
    ap.add_argument("--profile-annotations", action="store_true",
                    help="open jax.profiler.TraceAnnotation regions around "
                         "steps and atomics dispatch (needs --telemetry)")
    args = ap.parse_args()
    if args.telemetry:
        sink = (telemetry.RingBuffer() if args.telemetry == "ring"
                else telemetry.JsonlWriter(args.telemetry))
        telemetry.enable(sink, annotate=args.profile_annotations)
    else:
        telemetry.enable_from_env()
    chaos = FaultPlan.from_spec(args.chaos) if args.chaos else None
    tuning = None
    if args.tuning is not None:
        from repro.tuning import SpecController
        tuning = SpecController(
            state_path=None if args.tuning == "on" else args.tuning)
    try:
        out = train(args.arch, steps=args.steps, seq_len=args.seq_len,
                    global_batch=args.global_batch, reduced=not args.full,
                    ckpt_dir=args.ckpt_dir, lr=args.lr,
                    microbatches=args.microbatches, chaos=chaos,
                    tuning=tuning)
    finally:
        if telemetry.enabled():
            telemetry.disable()      # flush/close the JSONL capture
    print(json.dumps({k: v for k, v in out.items() if k != "history"}))


if __name__ == "__main__":
    main()
