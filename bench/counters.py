"""What the counter cells share: the pooled batches, the sample of fetched
values kept for the check, and the check against `reference.counters`.

A window runs batch ``k`` = pool batch ``k % P``.  The pool is drawn at
set-up on the host from the seed (YCSB's key chooser is float64 arithmetic
and a 64-bit hash) and copied to the device; the starting table is made on
the device from the seed, and made again after the window for the
reference.
"""

from __future__ import annotations

import random
from functools import partial
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import Check
from bench.reference import counters as ref
from bench.traffic import zipf


def seed_key(seed: int, stream: int = 0):
    """A raw threefry key from all the bits of ``seed`` (`PRNGKey` keeps
    only 32 of them)."""
    words = np.random.SeedSequence([int(seed), stream]).generate_state(2)
    return jnp.asarray(words, jnp.uint32)


@partial(jax.jit, static_argnames=("m", "low", "high"))
def start_table(key, *, m: int, low: int, high: int):
    """``m`` counters uniform in ``[low, high)``, ``high - low`` a power of
    two: the top bits of one random word each, so that no temporary as large
    as the table is made beside it."""
    bits = (high - low).bit_length() - 1
    if bits < 1 or (1 << bits) != high - low:
        raise ValueError(f"initial range [{low}, {high}) is not a power of "
                         "two wide")
    top = jax.random.bits(key, (m,), jnp.uint32) >> jnp.uint32(32 - bits)
    return top.astype(jnp.int32) + jnp.int32(low)


KEYS = {"zipf": zipf.scrambled_zipf_keys, "uniform": zipf.uniform_keys}


def make_pool(ctx, n: int, *, sharding=None):
    """(keys, values, distinct) of the cell's pool, ``n`` ops a batch: keys
    by the cell's ``keys`` chooser and values uniform in
    ``[-value_max, value_max]``, drawn on the host from the seed, one device
    array each; and the number of distinct keys in each batch."""
    cell, cfg = ctx.cell, ctx.config
    if cell["keys"] not in KEYS:
        raise ValueError(f"unknown key distribution {cell['keys']!r}")
    if cell["keys"] == "zipf" and cfg.get("zipf_constant") != zipf.YCSB_THETA:
        raise ValueError("YCSB's scrambled zipfian chooser is defined for "
                         f"the constant {zipf.YCSB_THETA} only")
    pool, vmax = int(cell["pool_batches"]), int(cell["value_max"])
    rng = np.random.default_rng([int(ctx.seed), 1])
    idx = KEYS[cell["keys"]](rng, pool * n, int(cfg["slots"])).reshape(pool,
                                                                       n)
    vals = rng.integers(-vmax, vmax + 1, (pool, n), dtype=np.int32)
    distinct = [int(np.unique(row).size) for row in idx]
    return ([jax.device_put(a, sharding) for a in idx],
            [jax.device_put(a, sharding) for a in vals], distinct)


class Sampler:
    """Keeps the fetched values of the first ``head`` batches, of ``size``
    batches drawn uniformly from the rest by reservoir sampling (seeded),
    and of the last batch."""

    def __init__(self, seed: int, head: int = 2, size: int = 3):
        size = max(size, 1)
        self.rng = random.Random(int(seed))
        self.head, self.size = head, size
        self.kept: Dict[int, object] = {}
        self.reservoir: List[int] = []
        self.seen = 0
        self.last = None

    def offer(self, k: int, fetched) -> None:
        self.last = (k, fetched)
        if k < self.head:
            self.kept[k] = fetched
            return
        self.seen += 1
        if len(self.reservoir) < self.size:
            self.reservoir.append(k)
            self.kept[k] = fetched
            return
        j = self.rng.randrange(self.seen)
        if j < self.size:
            del self.kept[self.reservoir[j]]
            self.reservoir[j] = k
            self.kept[k] = fetched

    def host(self) -> Dict[int, np.ndarray]:
        out = {k: np.asarray(v) for k, v in self.kept.items()}
        if self.last is not None:
            out[self.last[0]] = np.asarray(self.last[1])
        return out


def check(table0: np.ndarray, final: np.ndarray, pool_idx, pool_vals,
          batches: int, fetched: Dict[int, np.ndarray]) -> List[Check]:
    """The final table and the kept fetched values against the reference
    run of the same ``batches`` batches, op by op in arrival order."""
    want, want_fetched = ref.replay(table0, pool_idx, pool_vals, batches,
                                    set(fetched))
    table_diff = int(np.count_nonzero(np.asarray(final) != want))
    fetched_diff = sum(
        int(np.count_nonzero(np.asarray(got).reshape(-1) != want_fetched[k]))
        for k, got in fetched.items())
    return [Check("table_diff", table_diff, 0),
            Check("fetched_diff", fetched_diff, 0)]
