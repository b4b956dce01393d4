#!/usr/bin/env python3
"""Run the main path once on a TPU and check every result.

    python chip_smoke.py              # one chip: engine, pallas, bfs, model
    python chip_smoke.py --chips 4    # a 2x2 mesh: the sharded tier only

Phases (one chip):

  engine  `repro.atomics.execute` on a 2^24-slot int32 table (the parent
          array of a Graph500 scale-24 graph) with 2^20-op batches of
          uniform and Zipf(0.99) keys: FAA with fetch, uniform-expected CAS
          and MAX, on every registered backend that supports the case and
          on ``backend="auto"``.  Each result is compared bit for bit with
          `rmw_serialized` on the same batch.
  pallas  the Mosaic kernels: ``backend="pallas"`` FAA (table only and
          with fetch) and uniform-expected CAS with fetch on the largest
          float32 table the fetched kernel accepts, with values that one
          bf16 MXU pass would round, compared bit for bit with
          `rmw_serialized`, and the `slot_occupancy` counter kernel; each
          compiled program must hold a ``tpu_custom_call``.
  bfs     Graph500 Kronecker graph (edge factor 16), `bfs` with the CAS,
          SWP and FAA combiners from one root, each checked by
          `validate_parents`.
  model   mamba2_780m at its published config: `launch.train.train` for 3
          steps (finite losses), `launch.serve.BatchServer` answering 4
          requests, the SSD kernel against `ssd_chunked_jnp`, and the
          first decode step's logits against a prefill over the prompt plus
          that token.

With ``--chips 4`` the one phase is `sharded`: `atomics.execute` on an
`AtomicTable` sharded over a 2x2 mesh under each exchange strategy,
compared with `rmw_serialized` on the per-device batches concatenated in
device-rank order, and `bfs_sharded` against single-device `bfs`.

Each case prints one line with its sizes, compile seconds, wall seconds
and ``correct=``; each phase ends with a summary line.  The last line is a
JSON object ``{"ok": true, "device": {...}}`` and is printed only when
every phase was correct.  The script exits non-zero, printing no result,
when JAX sees no TPU.  Weights and data are random, made from ``--seed``.
Compile and wall seconds are one cold run's, printed for the record.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

ENGINE_SLOTS = 1 << 24          # Graph500 scale-24 parent array
ENGINE_OPS = 1 << 20
ZIPF_THETA = 0.99               # YCSB's zipfian constant
BFS_SCALE, BFS_EDGEFACTOR = 22, 16
MODEL = "mamba2_780m"
TRAIN_SEQ, TRAIN_BATCH, TRAIN_REMAT = 1024, 2, "full"
SSD_SHAPE = dict(b=2, s=2048)   # heads, head_dim, d_state from the config
SERVE_PROMPTS = (7, 33, 128, 300)
SERVE_MAX_NEW = 8
#: bf16 activations round independently on the two paths in each of 48
#: layers: ~sqrt(48) x 2^-8 of the logits' scale, doubled; a broken cache
#: path is off by O(1).
LOGIT_RTOL = 5e-2
#: the SSD kernel under "highest" is float32 math: at SSD_SHAPE the CPU
#: interpreter reads 9.4e-6 (y) and 1.0e-5 (final state) against
#: `ssd_chunked_jnp`; operands rounded to bf16 once (one MXU pass) read
#: 4.6e-3.  The limit sits between the two.
SSD_RTOL = 1e-4
SHARDED_SLOTS, SHARDED_OPS_PER_DEVICE = 1 << 22, 1 << 16
SHARDED_BFS_SCALE = 18


def log(line: str) -> None:
    print(line, flush=True)


def timed(fn, *args):
    """Compile ``fn`` ahead of time for ``args``, run it once, block.

    Returns (compile_s, wall_s, outputs, compiled)."""
    import jax
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    t1 = time.perf_counter()
    out = jax.block_until_ready(compiled(*args))
    return t1 - t0, time.perf_counter() - t1, out, compiled


def same(a, b) -> bool:
    return bool(np.array_equal(np.asarray(a), np.asarray(b)))


def n_diff(a, b) -> int:
    """How many elements differ (shapes that differ count as all)."""
    a, b = np.asarray(a), np.asarray(b)
    return int((a != b).sum()) if a.shape == b.shape else max(a.size, b.size)


def rel_err(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


def zipf_keys(rng, m: int, n: int, theta: float = ZIPF_THETA) -> np.ndarray:
    """YCSB's scrambled zipfian: rank r ~ r^-theta, ranks scattered over
    the key space by a fixed permutation."""
    cdf = np.cumsum(np.arange(1, m + 1, dtype=np.float64) ** -theta)
    ranks = np.searchsorted(cdf, rng.random(n) * cdf[-1])
    return rng.permutation(m)[np.minimum(ranks, m - 1)].astype(np.int32)


def engine_batch(rng, op: str, m: int, n: int):
    """(table0, values, expected) for one op kind, int32."""
    if op == "cas":
        table0 = np.where(rng.random(m) < 0.5, -1,
                          rng.integers(0, 4, m)).astype(np.int32)
        vals = rng.integers(-1, 4, n).astype(np.int32)  # -1 keeps a chain
        return table0, vals, -1
    table0 = rng.integers(-1000, 1000, m).astype(np.int32)
    if op == "max":
        return table0, rng.integers(-1 << 20, 1 << 20, n).astype(np.int32), 0
    return table0, rng.integers(-8, 9, n).astype(np.int32), 0


def make_op(kind: str, idx, vals, expected):
    import jax.numpy as jnp
    from repro import atomics
    if kind == "cas":
        return atomics.Cas(idx, vals, expected=jnp.asarray(expected,
                                                           vals.dtype))
    return atomics.OP_KINDS[kind](idx, vals)


def serialized_ref(kind, table0, idx, vals, expected):
    import jax.numpy as jnp
    from repro.core.rmw import rmw_serialized
    exp = jnp.full(idx.shape, expected, vals.dtype) if kind == "cas" else None
    return timed(lambda t, i, v: rmw_serialized(t, i, v, kind, exp),
                 table0, idx, vals)


# ---------------------------------------------------------------------------
# one-chip phases
# ---------------------------------------------------------------------------

def phase_engine(seed: int, m: int = ENGINE_SLOTS,
                 n: int = ENGINE_OPS) -> bool:
    import jax.numpy as jnp
    from repro import atomics
    from repro.core import rmw_engine

    rng = np.random.default_rng(seed)
    keys = {"uniform": rng.integers(0, m, n).astype(np.int32),
            "zipf": zipf_keys(rng, m, n)}
    ok_all, comp_all, wall_all, picks = True, 0.0, 0.0, {}
    for kind in ("faa", "cas", "max"):
        backends = [b.name for b in rmw_engine.BACKENDS.values()
                    if b.supports(kind, dtype=jnp.int32, m=m)]
        auto = rmw_engine.select_backend(kind, n, m, dtype=jnp.int32)
        picks[kind] = auto
        for dist, k in keys.items():
            table0, vals, expected = engine_batch(rng, kind, m, n)
            t0, i0, v0 = (jnp.asarray(table0), jnp.asarray(k),
                          jnp.asarray(vals))
            _, _, ref, _ = serialized_ref(kind, t0, i0, v0, expected)
            for backend in backends + ["auto"]:
                def fn(t, i, v, backend=backend):
                    res = atomics.execute(t, make_op(kind, i, v, expected),
                                          backend=backend)
                    return res.table.data, res.fetched, res.success
                c, w, (tab, fet, suc), _ = timed(fn, t0, i0, v0)
                diffs = (n_diff(tab, ref.table), n_diff(fet, ref.fetched),
                         n_diff(suc, ref.success))
                ok = not any(diffs)
                shown = f"auto({auto})" if backend == "auto" else backend
                log(f"engine/{kind} keys={dist} backend={shown} m={m} n={n} "
                    f"dtype=int32 compile_s={c:.3f} wall_s={w:.4f} "
                    f"diffs(table,fetched,success)={diffs} correct={ok}")
                ok_all &= ok
                comp_all += c
                wall_all += w
    log(f"phase=engine m={m} n={n} dtype=int32 auto={picks} "
        f"compile_s={comp_all:.3f} wall_s={wall_all:.4f} correct={ok_all}")
    return ok_all


def pallas_batch(rng, op: str, m: int, n: int):
    """(table0, values, expected) for one op kind, float32 integers of
    magnitude up to 2^16: with ~n/m ops per slot every sum stays below 2^24,
    exact in float32 in any order, and most are not exact in bfloat16, so
    one bf16 MXU pass in a kernel's contractions shows."""
    big = 1 << 16
    if op == "cas":
        table0 = np.where(rng.random(m) < 0.5, -1,
                          rng.integers(0, big, m))
        vals = np.where(rng.random(n) < 0.25, -1,        # -1 keeps a chain
                        rng.integers(0, big, n))
        return table0.astype(np.float32), vals.astype(np.float32), -1
    return (rng.integers(-big, big + 1, m).astype(np.float32),
            rng.integers(-big, big + 1, n).astype(np.float32), 0)


def phase_pallas(seed: int, n: int = ENGINE_OPS) -> bool:
    import jax.numpy as jnp
    from repro import atomics
    from repro.core.rmw_engine import PALLAS_FETCHED_MAX_SLOTS
    from repro.kernels.rmw import ops as kops

    m = PALLAS_FETCHED_MAX_SLOTS
    rng = np.random.default_rng(seed + 1)
    i0 = jnp.asarray(rng.integers(0, m, n), jnp.int32)
    ok_all, comp_all, wall_all = True, 0.0, 0.0
    for kind, need_fetched in (("faa", False), ("faa", True), ("cas", True)):
        table0, vals, expected = pallas_batch(rng, kind, m, n)
        t0, v0 = jnp.asarray(table0), jnp.asarray(vals)
        _, _, ref, _ = serialized_ref(kind, t0, i0, v0, expected)

        def fn(t, i, v, kind=kind, need_fetched=need_fetched,
               expected=expected):
            res = atomics.execute(t, make_op(kind, i, v, expected),
                                  backend="pallas",
                                  need_fetched=need_fetched)
            return res.table.data, res.fetched, res.success
        c, w, (tab, fet, suc), compiled = timed(fn, t0, i0, v0)
        mosaic = "tpu_custom_call" in compiled.as_text()
        diffs = (n_diff(tab, ref.table),) + (
            (n_diff(fet, ref.fetched), n_diff(suc, ref.success))
            if need_fetched else ())
        ok = mosaic and not any(diffs)
        log(f"pallas/{kind} need_fetched={need_fetched} m={m} n={n} "
            f"dtype=float32 tpu_custom_call={mosaic} compile_s={c:.3f} "
            f"wall_s={w:.4f} diffs={diffs} correct={ok}")
        ok_all &= ok
        comp_all += c
        wall_all += w
    c, w, occ, compiled = timed(lambda i: kops.slot_occupancy(i, m), i0)
    mosaic = "tpu_custom_call" in compiled.as_text()
    d = n_diff(occ, np.bincount(np.asarray(i0), minlength=m))
    ok = mosaic and not d
    log(f"pallas/slot_occupancy m={m} n={n} tpu_custom_call={mosaic} "
        f"compile_s={c:.3f} wall_s={w:.4f} diffs={d} correct={ok}")
    ok_all &= ok
    log(f"phase=pallas m={m} (largest fetched table) n={n} "
        f"compile_s={comp_all + c:.3f} wall_s={wall_all + w:.4f} "
        f"correct={ok_all}")
    return ok_all


def phase_bfs(seed: int, scale: int = BFS_SCALE,
              edgefactor: int = BFS_EDGEFACTOR) -> bool:
    from repro.core.bfs import bfs, kronecker_graph, validate_parents

    t = time.perf_counter()
    src, dst = kronecker_graph(scale, edgefactor=edgefactor, seed=seed)
    src, dst = src.astype(np.int32), dst.astype(np.int32)
    n, root = 1 << scale, int(src[0])
    log(f"bfs/graph scale={scale} edgefactor={edgefactor} n={n} "
        f"edges={len(src)} root={root} gen_s={time.perf_counter() - t:.1f}")
    ok_all, comp_all, wall_all = True, 0.0, 0.0
    for op in ("cas", "swp", "faa"):
        t0 = time.perf_counter()
        bfs(src, dst, n, root=root, op=op)           # compile + run
        t1 = time.perf_counter()
        res = bfs(src, dst, n, root=root, op=op)     # run
        t2 = time.perf_counter()
        parent = np.asarray(res.parent)
        ok = validate_parents(src, dst, parent, root)
        c, w = (t1 - t0) - (t2 - t1), t2 - t1
        log(f"bfs/{op} scale={scale} levels={res.levels} "
            f"reached={int((parent >= 0).sum())} "
            f"edges_traversed={res.edges_traversed} compile_s={c:.3f} "
            f"wall_s={w:.4f} correct={ok}")
        ok_all &= ok
        comp_all += c
        wall_all += w
    log(f"phase=bfs scale={scale} edgefactor={edgefactor} "
        f"compile_s={comp_all:.3f} wall_s={wall_all:.4f} correct={ok_all}")
    return ok_all


def _train_case(seed: int, seq: int, batch: int, remat: str) -> bool:
    """`launch.train.train` at the published config; the same train step
    is compiled ahead of time first for its memory and its Mosaic call
    (the persistent cache then serves train()'s own compile)."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.launch.steps import make_train_step
    from repro.launch.train import train
    from repro.models.model import build_model
    from repro.optim.adamw import AdamWConfig, init_state

    steps = 3
    cfg = get_config(MODEL)
    model = build_model(cfg, attn_impl="chunked", remat_policy=remat,
                        loss_chunk=2048)
    opt_cfg = AdamWConfig(lr=3e-4, warmup_steps=min(20, steps // 5 + 1),
                          total_steps=steps)
    # shapes placed as train()'s arrays are, so that its compile of the
    # same step is a persistent-cache hit
    dev = jax.sharding.SingleDeviceSharding(jax.devices()[0])

    def placed(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=dev), tree)
    params = placed(jax.eval_shape(model.init, jax.random.PRNGKey(seed)))
    opt = placed(jax.eval_shape(lambda p: init_state(p, opt_cfg), params))
    tok = jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=dev)
    t = time.perf_counter()
    compiled = jax.jit(make_train_step(model, opt_cfg),
                       donate_argnums=(0, 1)).lower(
        params, opt, {"tokens": tok, "labels": tok}).compile()
    aot_s = time.perf_counter() - t
    mem = compiled.memory_analysis()
    peak = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    mosaic = "tpu_custom_call" in compiled.as_text()
    del compiled

    t = time.perf_counter()
    out = train(MODEL, reduced=False, steps=steps, seq_len=seq,
                global_batch=batch, remat_policy=remat, log_every=1,
                seed=seed)
    wall = time.perf_counter() - t
    losses = [h["loss"] for h in out["history"]]
    secs = [h["sec"] for h in out["history"]]
    ok = (mosaic and len(losses) == steps
          and bool(np.isfinite(losses).all()))
    log(f"model/train arch={MODEL} layers={cfg.n_layers} "
        f"d_model={cfg.d_model} vocab={cfg.vocab_size} seq_len={seq} "
        f"batch={batch} remat={remat} "
        f"step_program_gib={peak / 2**30:.2f} tpu_custom_call={mosaic} "
        f"losses={[round(x, 4) for x in losses]} "
        f"compile_s={aot_s:.3f} step_s={[round(s, 3) for s in secs]} "
        f"wall_s={wall:.3f} correct={ok}")
    return ok


def _ssd_case(seed: int, b: int, s: int) -> bool:
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.kernels.ssd.ops import ssd, ssd_chunked_jnp

    sc = get_config(MODEL).ssm
    d = get_config(MODEL).d_model
    h, p, n = sc.n_heads(d), sc.head_dim, sc.d_state
    rng = np.random.default_rng(seed + 2)
    x = jnp.asarray(rng.normal(size=(b, s, h, p)), jnp.float32)
    dt = jnp.asarray(rng.uniform(1e-3, 0.1, size=(b, s, h)), jnp.float32)
    a = jnp.asarray(-rng.uniform(1.0, 16.0, size=(h,)), jnp.float32)
    bm = jnp.asarray(rng.normal(size=(b, s, h, n)), jnp.float32)
    cm = jnp.asarray(rng.normal(size=(b, s, h, n)), jnp.float32)
    args = (x, dt, a, bm, cm)
    ok_all = True
    with jax.default_matmul_precision("highest"):
        for final in (False, True):
            def kern(*a_, final=final):
                return ssd(*a_, chunk=sc.chunk, use_kernel=True,
                           return_final_state=final)

            def ref(*a_, final=final):
                return ssd_chunked_jnp(*a_, chunk=sc.chunk,
                                       return_final_state=final)
            c, w, got, compiled = timed(kern, *args)
            _, _, want, _ = timed(ref, *args)
            mosaic = "tpu_custom_call" in compiled.as_text()
            got, want = (got, want) if final else ((got,), (want,))
            errs = [rel_err(g, r) for g, r in zip(got, want)]
            ok = mosaic and all(np.isfinite(errs)) \
                and max(errs) <= SSD_RTOL
            log(f"model/ssd_kernel b={b} s={s} heads={h} head_dim={p} "
                f"d_state={n} chunk={sc.chunk} return_final_state={final} "
                f"max_rel_err={['%.2e' % e for e in errs]} "
                f"tpu_custom_call={mosaic} compile_s={c:.3f} "
                f"wall_s={w:.4f} correct={ok}")
            ok_all &= ok
    return ok_all


def _serve_case(seed: int) -> bool:
    import jax.numpy as jnp
    from repro.launch.serve import BatchServer, Request

    t = time.perf_counter()
    server = BatchServer(MODEL, reduced=False, slots=4, s_max=512,
                         seed=seed)
    vocab = server.cfg.vocab_size
    rng = np.random.default_rng(seed + 3)
    reqs = [Request(rid=i, prompt=rng.integers(0, vocab, L).tolist(),
                    max_new=SERVE_MAX_NEW)
            for i, L in enumerate(SERVE_PROMPTS)]
    stats = server.run(reqs)
    wall = time.perf_counter() - t
    ok = (stats["completed"] == len(reqs)
          and all(len(r.out) == SERVE_MAX_NEW for r in reqs)
          and all(0 <= tk < vocab for r in reqs for tk in r.out))
    log(f"model/serve arch={MODEL} requests={len(reqs)} "
        f"prompt_lens={list(SERVE_PROMPTS)} max_new={SERVE_MAX_NEW} "
        f"tokens={stats['tokens']} completed={stats['completed']} "
        f"wall_s={wall:.3f} correct={ok}")

    # the cache path against the full forward pass: first decode step vs a
    # prefill over the prompt plus that token, at the last position
    prompt = reqs[1].prompt
    model, params = server.model, server.params
    cache, logits0 = model.prefill(
        params, {"tokens": jnp.asarray([prompt], jnp.int32)}, server.s_max)
    tok = int(jnp.argmax(logits0, -1)[0])
    _, dec = server._decode(params, cache,
                            {"tokens": jnp.asarray([[tok]], jnp.int32)})
    _, full = model.prefill(
        params, {"tokens": jnp.asarray([prompt + [tok]], jnp.int32)},
        server.s_max)
    err = rel_err(dec, full)
    ok2 = bool(np.isfinite(err)) and err <= LOGIT_RTOL
    log(f"model/decode_vs_prefill prompt_len={len(prompt)} "
        f"max_rel_err={err:.2e} rtol={LOGIT_RTOL} correct={ok2}")
    return ok and ok2


def phase_model(seed: int) -> bool:
    t = time.perf_counter()
    ok = _train_case(seed, TRAIN_SEQ, TRAIN_BATCH, TRAIN_REMAT)
    ok &= _serve_case(seed)
    ok &= _ssd_case(seed, **SSD_SHAPE)
    log(f"phase=model arch={MODEL} wall_s={time.perf_counter() - t:.3f} "
        f"correct={ok}")
    return ok


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def phase_sharded(seed: int, m: int = SHARDED_SLOTS,
                  n_per: int = SHARDED_OPS_PER_DEVICE,
                  bfs_scale: int = SHARDED_BFS_SCALE) -> bool:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro import atomics
    from repro.core.bfs import bfs, bfs_sharded, kronecker_graph
    from repro.sharding import make_mesh, shard_map_compat

    devs = jax.devices()[:4]
    mesh = make_mesh((2, 2), ("pod", "dev"), devices=devs)
    axis = ("pod", "dev")
    spec = P(axis)
    ndev = len(devs)
    rng = np.random.default_rng(seed + 4)
    keys = {"uniform": rng.integers(0, m, (ndev, n_per)).astype(np.int32),
            "zipf": zipf_keys(rng, m, ndev * n_per).reshape(ndev, n_per)}
    cases = [("faa", s, True, d) for d in keys
             for s in ("oneshot", "hierarchical", "naive")]
    cases += [("faa", "dense", False, d) for d in keys]
    cases += [("cas", s, True, "zipf")
              for s in ("oneshot", "hierarchical", "naive")]
    refs = {}
    ok_all, comp_all, wall_all = True, 0.0, 0.0
    for kind, strategy, need_fetched, dist in cases:
        if (kind, dist) not in refs:
            table0, vals, expected = engine_batch(rng, kind, m,
                                                  ndev * n_per)
            vals = vals.reshape(ndev, n_per)
            args = (jnp.asarray(table0), jnp.asarray(keys[dist]),
                    jnp.asarray(vals))
            _, _, ref, _ = serialized_ref(kind, args[0],
                                          args[1].reshape(-1),
                                          args[2].reshape(-1), expected)
            put = tuple(jax.device_put(a, NamedSharding(mesh, spec))
                        for a in args)
            refs[kind, dist] = (put, ref, expected)
        put, ref, expected = refs[kind, dist]
        spread = {s.device for s in put[0].addressable_shards}

        def body(t, i, v, kind=kind, strategy=strategy,
                 need_fetched=need_fetched, expected=expected):
            tbl = atomics.AtomicTable(t, axis=axis)
            res = atomics.execute(tbl, make_op(kind, i[0], v[0], expected),
                                  strategy=strategy,
                                  need_fetched=need_fetched)
            return res.table.data, res.fetched[None], res.success[None]
        fn = shard_map_compat(body, mesh, (spec, spec, spec),
                              (spec, spec, spec))
        c, w, (tab, fet, suc), _ = timed(fn, *put)
        out_spread = {s.device for s in tab.addressable_shards}
        ok = (len(spread) == ndev and len(out_spread) == ndev
              and same(tab, ref.table))
        if need_fetched:
            ok &= same(np.asarray(fet).reshape(-1), ref.fetched)
            ok &= same(np.asarray(suc).reshape(-1), ref.success)
        log(f"sharded/{kind} strategy={strategy} need_fetched={need_fetched} "
            f"keys={dist} mesh=2x2 m={m} n_per_device={n_per} "
            f"table_devices={len(out_spread)} compile_s={c:.3f} "
            f"wall_s={w:.4f} correct={ok}")
        ok_all &= ok
        comp_all += c
        wall_all += w

    src, dst = kronecker_graph(bfs_scale, edgefactor=BFS_EDGEFACTOR,
                               seed=seed)
    n, root = 1 << bfs_scale, int(src[0])
    local = np.asarray(bfs(src, dst, n, root=root, op="cas").parent)
    for op in ("cas", "swp"):
        t = time.perf_counter()
        res = bfs_sharded(src, dst, n, root=root, mesh=make_mesh(
            (ndev,), ("dev",), devices=devs), op=op)
        w = time.perf_counter() - t
        ok = same(res.parent, local)
        log(f"sharded/bfs_{op} scale={bfs_scale} "
            f"edgefactor={BFS_EDGEFACTOR} devices={ndev} levels={res.levels} "
            f"first_call_s={w:.3f} parents_match_single_device={ok} "
            f"correct={ok}")
        ok_all &= ok
    log(f"phase=sharded mesh=2x2 m={m} n_per_device={n_per} "
        f"compile_s={comp_all:.3f} wall_s={wall_all:.4f} correct={ok_all}")
    return ok_all


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs the sharded phase on a 2x2 mesh, only")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    src = os.path.join(HERE, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no repro package under {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    import jax

    from repro.launch.cache import setup_compile_cache
    cache_dir = setup_compile_cache()

    devices = jax.devices()
    d0 = devices[0]
    log(f"platform={d0.platform} device_kind={d0.device_kind} "
        f"count={len(devices)} compile_cache={cache_dir}")
    if d0.platform != "tpu":
        print("chip_smoke: JAX sees no TPU; nothing was run",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX sees {len(devices)}", file=sys.stderr)
        return 1

    phases = ([phase_sharded] if args.chips == 4 else
              [phase_engine, phase_pallas, phase_bfs, phase_model])
    ok = True
    for phase in phases:
        name = phase.__name__[len("phase_"):]
        try:
            ok &= phase(args.seed)
        except Exception:  # noqa: BLE001 — report, then fail the run
            traceback.print_exc()
            log(f"phase={name} correct=False error=see stderr")
            ok = False
    if not ok:
        return 1
    log(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
