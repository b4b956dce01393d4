"""Bounded-retry CAS loops: `atomics.execute_until`.

The contract under serialized-equivalence semantics: a fully-contended
batch (every op targeting one slot) resolves exactly one op per round, so
n ops converge in <= n rounds for the immediate and exponential-spacing
policies; `ShrinkBatch` trades rounds for fewer total attempts.  Local and
sharded tiers must produce identical round histories.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import atomics
from repro.atomics import (Cas, ExponentialBackoff, Faa, ImmediateRetry,
                           RetryPolicy, ShrinkBatch, execute_until)
from repro.sharding import make_mesh


def _contended_make_ops(n, slot=0):
    """n CAS increments all fighting over one slot — the textbook CAS
    loop ``CAS(x, v, v + 1)``: every op expects the same pre-image, so
    each round serializes exactly one winner and the rest retry with the
    fetched value as their next ``expected``."""
    idx0 = jnp.zeros((n,), jnp.int32) + slot

    def make_ops(slots, observed):
        if slots is None:
            return Cas(idx0, jnp.ones((n,), jnp.int32),
                       expected=jnp.zeros((n,), jnp.int32))
        return Cas(jnp.asarray(slots), jnp.asarray(observed) + 1,
                   expected=jnp.asarray(observed))
    return make_ops


def test_fully_contended_resolves_in_n_rounds_immediate():
    for n in (1, 4, 16):
        t = atomics.AtomicTable(jnp.zeros((8,), jnp.int32))
        res = execute_until(t, _contended_make_ops(n), max_rounds=n,
                            policy="immediate")
        assert res.pending.size == 0, f"n={n}: ops left unresolved"
        assert res.n_rounds <= n
        assert res.success.all()
        # serialized equivalence: exactly one winner per round
        assert sorted(res.rounds.tolist()) == list(range(1, n + 1))
        # the chained increments commuted to a final value of n
        assert int(np.asarray(res.table.data)[0]) == n


def test_exponential_policy_also_bounded_by_n():
    n = 8
    t = atomics.AtomicTable(jnp.zeros((4,), jnp.int32))
    slept = []
    res = execute_until(t, _contended_make_ops(n), max_rounds=n,
                        policy=ExponentialBackoff(base_s=1e-5, factor=2.0,
                                                  max_s=1e-4),
                        sleep_fn=slept.append)
    assert res.pending.size == 0 and res.n_rounds <= n
    assert len(slept) == res.n_rounds - 1          # a delay between rounds
    assert slept == sorted(slept)                  # non-decreasing spacing
    assert max(slept) <= 1e-4 + 1e-12


def test_shrink_batch_issues_fewer_attempts():
    n = 16
    runs = {}
    for name, policy in (("immediate", "immediate"),
                         ("shrink", ShrinkBatch(factor=0.5, min_batch=1))):
        t = atomics.AtomicTable(jnp.zeros((4,), jnp.int32))
        res = execute_until(t, _contended_make_ops(n), max_rounds=4 * n,
                            policy=policy)
        assert res.pending.size == 0
        assert int(np.asarray(res.table.data)[0]) == n
        runs[name] = res
    # total attempts = sum over ops of rounds they were in flight; the
    # shrink policy's whole point (arxiv 1305.5800) is to spend fewer
    attempts = {k: int(r.rounds.sum()) for k, r in runs.items()}
    assert attempts["shrink"] < attempts["immediate"]


def test_uncontended_batch_one_round():
    t = atomics.AtomicTable(jnp.asarray(np.arange(8), jnp.int32))
    idx = jnp.asarray([0, 3, 5], jnp.int32)
    res = execute_until(
        t, lambda s, o: Cas(idx, jnp.asarray([10, 13, 15], jnp.int32),
                            expected=jnp.asarray([0, 3, 5], jnp.int32)),
        max_rounds=8)
    assert res.n_rounds == 1 and res.success.all()
    np.testing.assert_array_equal(np.asarray(res.table.data)[[0, 3, 5]],
                                  [10, 13, 15])


def test_max_rounds_exhaustion_reports_pending():
    n, budget = 16, 5
    t = atomics.AtomicTable(jnp.zeros((4,), jnp.int32))
    res = execute_until(t, _contended_make_ops(n), max_rounds=budget)
    assert res.n_rounds == budget
    assert int(res.success.sum()) == budget        # one winner per round
    assert res.pending.size == n - budget
    # losers report the budget as their round count, winners their round
    assert (res.rounds[res.pending] == budget).all()
    assert int(np.asarray(res.table.data)[0]) == budget


def test_make_ops_none_gives_up_early():
    n = 8
    base = _contended_make_ops(n)

    def capped(slots, observed):
        if slots is not None and len(slots) <= n - 3:
            return None                            # caller bails
        return base(slots, observed)

    t = atomics.AtomicTable(jnp.zeros((4,), jnp.int32))
    res = execute_until(t, capped, max_rounds=4 * n)
    assert res.pending.size == n - 3
    assert int(res.success.sum()) == 3


def test_values_only_retry_return():
    """make_ops may return a bare values array: the combinator re-issues
    CAS at the same slots with expected := the observed pre-images."""
    n = 6
    t = atomics.AtomicTable(jnp.zeros((4,), jnp.int32))

    def make_ops(slots, observed):
        if slots is None:
            return Cas(jnp.zeros((n,), jnp.int32),
                       jnp.ones((n,), jnp.int32),
                       expected=jnp.zeros((n,), jnp.int32))
        return jnp.asarray(observed) + 1           # values only
    res = execute_until(t, make_ops, max_rounds=n)
    assert res.pending.size == 0
    assert int(np.asarray(res.table.data)[0]) == n


def test_non_cas_op_resolves_in_one_round():
    t = atomics.AtomicTable(jnp.zeros((8,), jnp.int32))
    idx = jnp.asarray([1, 1, 2], jnp.int32)
    res = execute_until(t, lambda s, o: Faa(idx, jnp.ones((3,), jnp.int32)),
                        max_rounds=4)
    assert res.n_rounds == 1 and res.success.all()
    assert int(np.asarray(res.table.data)[1]) == 2


def test_validation_errors():
    t = atomics.AtomicTable(jnp.zeros((4,), jnp.int32))
    with pytest.raises(ValueError, match="max_rounds"):
        execute_until(t, _contended_make_ops(2), max_rounds=0)
    with pytest.raises(ValueError, match="unknown retry policy"):
        execute_until(t, _contended_make_ops(2), policy="warp-speed")
    with pytest.raises(TypeError, match="op batch"):
        execute_until(t, lambda s, o: "nope", max_rounds=2)
    with pytest.raises(ValueError, match="factor"):
        ShrinkBatch(factor=0.0)
    assert ShrinkBatch(min_batch=0).min_batch == 1   # clamped, not rejected


def test_policy_registry_and_base_class():
    assert set(atomics.POLICIES) >= {"immediate", "shrink", "exponential"}
    for p in atomics.POLICIES.values():
        assert isinstance(p(), RetryPolicy)
    assert isinstance(ImmediateRetry(), RetryPolicy)


def test_sharded_single_device_parity():
    """Same contended batch through the sharded tier on a 1-device mesh:
    identical round history and final table to the local tier."""
    n = 8
    local = execute_until(atomics.AtomicTable(jnp.zeros((8,), jnp.int32)),
                          _contended_make_ops(n), max_rounds=n)
    mesh = make_mesh((1,), ("dev",))
    data = jax.device_put(
        jnp.zeros((8,), jnp.int32),
        jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("dev")))
    t = atomics.AtomicTable(data, axis="dev")
    res = execute_until(t, _contended_make_ops(n), max_rounds=n)
    assert res.n_rounds == local.n_rounds
    np.testing.assert_array_equal(res.rounds, local.rounds)
    np.testing.assert_array_equal(np.asarray(res.table.data),
                                  np.asarray(local.table.data))


_SHARDED_SCRIPT = r"""
import json, os
import jax, jax.numpy as jnp, numpy as np
from repro import atomics
from repro.atomics import Cas, execute_until

from repro.sharding import make_mesh
mesh = make_mesh((2, 4), ("pod", "dev"))
P = jax.sharding.PartitionSpec
data = jax.device_put(jnp.zeros((32,), jnp.int32),
                      jax.sharding.NamedSharding(mesh, P(("pod", "dev"))))
t = atomics.AtomicTable(data, axis=("pod", "dev"))

n = 16
def make_ops(slots, observed):
    if slots is None:
        return Cas(jnp.zeros((n,), jnp.int32), jnp.ones((n,), jnp.int32),
                   expected=jnp.zeros((n,), jnp.int32))
    return Cas(jnp.asarray(slots), jnp.asarray(observed) + 1,
               expected=jnp.asarray(observed))

res = execute_until(t, make_ops, max_rounds=n)
out = {"n_rounds": int(res.n_rounds),
       "pending": int(res.pending.size),
       "rounds": sorted(np.asarray(res.rounds).tolist()),
       "final": int(np.asarray(res.table.data)[0])}
print("RESULT:" + json.dumps(out))
"""


def test_sharded_8dev_contended_bounded(tmp_path):
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [os.path.abspath("src")] +
                   os.environ.get("PYTHONPATH", "").split(os.pathsep)))
    proc = subprocess.run([sys.executable, "-c", _SHARDED_SCRIPT],
                          capture_output=True, text=True, env=env,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = [l for l in proc.stdout.splitlines()
            if l.startswith("RESULT:")][0]
    out = json.loads(line[len("RESULT:"):])
    assert out["pending"] == 0
    assert out["n_rounds"] <= 16                   # the <= n bound, sharded
    assert out["rounds"] == list(range(1, 17))     # one winner per round
    assert out["final"] == 16
