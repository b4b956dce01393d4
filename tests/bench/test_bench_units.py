"""The yardstick's pieces on the CPU: peaks, least bytes, the generators
against their definitions, and the references against the program's
serialized oracle and a plain BFS."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bench import bytes as least
from bench import peaks
from bench.counters import Sampler, seed_key
from bench.reference import bfs as ref_bfs
from bench.reference import controls
from bench.reference import counters as ref_counters
from bench.traffic import kronecker, zipf


def test_peaks_known_and_unknown_kind():
    v5e = peaks.peaks("TPU v5 lite")
    assert v5e.hbm_bytes_per_s == 819e9
    assert v5e.bf16_flops == 197e12 and v5e.int8_ops == 393e12
    with pytest.raises(KeyError, match="no peaks"):
        peaks.peaks("TPU v9 imaginary")


def test_least_bytes_from_shapes_and_counts():
    # a pooled batch: 2^20 ops, each reads index and value and writes its
    # fetched word; 300,000 distinct slots read and written once
    assert least.counter_batch(1 << 20, 300_000) == \
        (1 << 20) * 12 + 300_000 * 8
    assert least.counter_batch(10, 4, fetched=False) == 10 * 8 + 4 * 8
    # a traversal of 2^20 directed edges over 2^16 vertices
    assert least.traversal(1 << 20, 1 << 16) == (1 << 20) * 8 + (1 << 16) * 4


@pytest.mark.parametrize("n", [2, 1000, 1 << 16, (1 << 16) + 12345])
def test_zeta_matches_direct_sum(n):
    direct = float(np.sum(np.arange(1, n + 1, dtype=np.float64) ** -0.99))
    assert zipf.zeta(n, 0.99) == pytest.approx(direct, rel=1e-13)


def test_zeta_tail_matches_direct_sum_large():
    n = 3 << 20
    direct = float(np.sum(np.arange(1, n + 1, dtype=np.float64) ** -0.99))
    assert zipf.zeta(n, 0.99) == pytest.approx(direct, rel=1e-12)


def test_ycsb_zetan_is_zeta_of_its_item_count():
    """YCSB's precomputed ZETAN is zeta(10^10, 0.99)."""
    assert zipf.zeta(zipf.ITEM_COUNT, zipf.YCSB_THETA) == pytest.approx(
        zipf.ZETAN, rel=1e-10)


def java_fnvhash64(val: int) -> int:
    """YCSB's ``Utils.fnvhash64`` step by step, in Java's 64-bit longs."""
    mask = (1 << 64) - 1
    h = 0xCBF29CE484222325
    for _ in range(8):
        h ^= val & 0xFF
        val >>= 8
        h = (h * 1099511628211) & mask
    h = h - (1 << 64) if h >> 63 else h
    return h if h == -(1 << 63) else abs(h)


@pytest.mark.parametrize("val", [0, 1, 255, 256, 2**26 + 3, 9_999_999_999,
                                 10_000_000_000])
def test_fnvhash64_matches_java(val):
    got = zipf.fnvhash64(np.array([val], np.int64))[0]
    assert int(got) == java_fnvhash64(val)


def test_zipf_rank_frequencies():
    gen = zipf.ZipfianGenerator(1 << 12)
    r = gen.ranks(np.random.default_rng(1).random(1 << 20))
    freq = np.bincount(r, minlength=gen.items) / r.size
    prob = gen.probabilities(16)
    # rank 0 near 1/zeta(n); the top ranks within 4 standard errors
    sd = np.sqrt(prob * (1 - prob) / r.size)
    assert np.all(np.abs(freq[:16] - prob) < 4 * sd + 1e-12)
    assert freq[0] == pytest.approx(1 / gen.zetan, rel=0.02)
    # the slope of log-frequency over log-rank is about -theta
    ranks = np.arange(2, 200)
    slope = np.polyfit(np.log(ranks + 1), np.log(freq[ranks]), 1)[0]
    assert slope == pytest.approx(-0.99, abs=0.08)


def test_scrambled_keys_as_ycsb_draws_them():
    """Ranks over 10^10 with ZETAN, folded by the hash modulo
    recordcount + 1, the key past the last drawn again: the hottest key
    takes about 1/ZETAN of the draws, and about a quarter of the ranks lie
    past 2^26."""
    rc, size = 1 << 20, 1 << 20
    keys = zipf.scrambled_zipf_keys(np.random.default_rng(5), size, rc)
    assert keys.dtype == np.int32 and keys.min() >= 0 and keys.max() < rc
    counts = np.sort(np.bincount(keys, minlength=rc))[::-1]
    top = np.array([1.0, 0.5 ** 0.99]) / zipf.ZETAN
    sd = np.sqrt(top * (1 - top) / size)
    assert np.all(np.abs(counts[:2] / size - top) < 4 * sd + 2e-5)
    # the first draws are the fold of the same uniforms, one by one
    u = np.random.default_rng(5).random(size)
    gen = zipf.ZipfianGenerator(zipf.ITEM_COUNT + 1, 0.99, zipf.ZETAN)
    r = gen.ranks(u[:200])
    want = [java_fnvhash64(int(x)) % (rc + 1) for x in r]
    assert [k for k, w in zip(keys[:200], want) if w < rc] == \
        [w for w in want if w < rc]
    past = np.mean(gen.ranks(u) >= 1 << 26)
    assert past == pytest.approx(1 - zipf.zeta(1 << 26, 0.99) / zipf.ZETAN,
                                 abs=0.005)


def test_scrambled_keys_never_the_key_past_the_last():
    keys = zipf.scrambled_zipf_keys(np.random.default_rng(2), 1 << 16, 3)
    assert set(np.unique(keys)) == {0, 1, 2}


def test_seed_key_uses_every_bit():
    a = np.asarray(seed_key(5))
    b = np.asarray(seed_key(5 + (1 << 40)))
    assert not np.array_equal(a, b)
    assert np.array_equal(a, np.asarray(seed_key(5)))


def test_kronecker_edge_count_and_bit_probabilities():
    scale, ef = 12, 16
    src, dst = kronecker.edges(jax.random.PRNGKey(3), scale=scale,
                               edgefactor=ef, permute=False)
    src, dst = np.asarray(src), np.asarray(dst)
    assert src.shape == dst.shape == (ef << scale,)
    a, b, c = kronecker.GRAPH500_ABC
    for level in (0, scale // 2, scale - 1):
        si = (src >> level) & 1
        di = (dst >> level) & 1
        assert si.mean() == pytest.approx(1 - a - b, abs=0.01)
        assert di.mean() == pytest.approx(b + (1 - a - b - c), abs=0.01)
        assert ((si == 0) & (di == 0)).mean() == pytest.approx(a, abs=0.01)


def test_kronecker_degree_skew_and_permutation():
    scale, ef = 12, 16
    key = jax.random.PRNGKey(4)
    src, dst = kronecker.edges(key, scale=scale, edgefactor=ef,
                               permute=False)
    deg = np.bincount(np.asarray(src), minlength=1 << scale)
    # vertex 0 draws source bit 0 at every level: E * (A + B)^scale
    expect = (ef << scale) * (0.57 + 0.19) ** scale
    assert deg[0] == pytest.approx(expect, rel=0.2)
    assert deg.max() > 20 * deg.mean()
    ps, pd = kronecker.edges(key, scale=scale, edgefactor=ef)
    pdeg = np.bincount(np.asarray(ps), minlength=1 << scale)
    assert np.array_equal(np.sort(pdeg), np.sort(deg))    # labels permuted
    s2, d2 = kronecker.symmetrise(ps, pd)
    assert np.array_equal(np.asarray(s2), np.r_[ps, pd])
    live = np.asarray(kronecker.has_edge(ps, pd, n=1 << scale))
    nonloop = np.asarray(ps) != np.asarray(pd)
    want = np.zeros(1 << scale, bool)
    want[np.asarray(ps)[nonloop]] = True
    want[np.asarray(pd)[nonloop]] = True
    assert np.array_equal(live, want)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_counter_reference_matches_rmw_serialized(seed):
    from repro.core.rmw import rmw_serialized
    rng = np.random.default_rng(seed)
    m, n = 64, 500
    table = rng.integers(-2**31, 2**31, m).astype(np.int32)
    idx = rng.integers(0, m, n).astype(np.int32)
    vals = rng.integers(-2**15, 2**15 + 1, n).astype(np.int32)
    new, fetched = ref_counters.faa(table, idx, vals)
    want = rmw_serialized(jnp.asarray(table), jnp.asarray(idx),
                          jnp.asarray(vals), "faa", None)
    assert np.array_equal(new, np.asarray(want.table))
    assert np.array_equal(fetched, np.asarray(want.fetched))


def test_replay_matches_batches_run_in_turn():
    rng = np.random.default_rng(9)
    m, n, pool = 32, 40, 3
    table0 = rng.integers(-2**31, 2**31, m).astype(np.int32)
    idx = [rng.integers(0, m, n).astype(np.int32) for _ in range(pool)]
    vals = [rng.integers(-2**15, 2**15, n).astype(np.int32)
            for _ in range(pool)]
    wanted = {0, 4, 5, 13, 29}           # gaps shorter and longer than P
    final, fetched = ref_counters.replay(table0, idx, vals, 30, wanted)
    table = table0
    for k in range(30):
        table, want = ref_counters.faa(table, idx[k % pool], vals[k % pool])
        if k in fetched:
            assert np.array_equal(fetched[k], want)
    assert set(fetched) == wanted
    assert np.array_equal(final, table)


def test_counter_control_breaks_only_the_order():
    rng = np.random.default_rng(4)
    table = rng.integers(0, 100, 16).astype(np.int32)
    idx = rng.integers(0, 16, 200).astype(np.int32)
    vals = rng.integers(1, 9, 200).astype(np.int32)
    new, fetched = ref_counters.faa(table, idx, vals)
    c_new, c_fetched = controls.faa_reverse_arrival(
        jnp.asarray(table), jnp.asarray(idx), jnp.asarray(vals))
    assert np.array_equal(np.asarray(c_new), new)
    assert not np.array_equal(np.asarray(c_fetched), fetched)
    # the reversed order is itself a serial order: the reference on the
    # reversed batch
    _, rev = ref_counters.faa(table, idx[::-1], vals[::-1])
    assert np.array_equal(np.asarray(c_fetched), rev[::-1])


def plain_bfs(src, dst, n, root):
    """One edge at a time, in list order, level by level."""
    parent = [-1] * n
    parent[root] = root
    frontier, levels = {root}, 0
    while True:
        levels += 1
        nxt = set()
        for s, d in zip(src, dst):
            if s in frontier and parent[d] == -1:
                parent[d] = int(s)
                nxt.add(int(d))
        if not nxt:
            return np.array(parent, np.int32), levels
        frontier = nxt


@pytest.mark.parametrize("seed", [0, 1])
def test_bfs_reference_matches_plain_bfs_and_program(seed):
    from repro.core.bfs import bfs
    scale = 7
    src, dst = kronecker.edges(jax.random.PRNGKey(seed), scale=scale,
                               edgefactor=8)
    s2, d2 = (np.asarray(a) for a in kronecker.symmetrise(src, dst))
    n = 1 << scale
    live = np.asarray(kronecker.has_edge(src, dst, n=n))
    for root in np.flatnonzero(live)[:3]:
        parent, levels = ref_bfs.bfs(s2, d2, n, int(root))
        want, want_levels = plain_bfs(s2.tolist(), d2.tolist(), n, int(root))
        assert np.array_equal(parent, want) and levels == want_levels
        got = bfs(s2, d2, n, root=int(root), op="cas")
        assert np.array_equal(np.asarray(got.parent), parent)
        assert got.levels == levels
        control = controls.bfs_last_arrival(s2, d2, n, root=int(root))
        assert control.levels == levels
        assert np.array_equal(np.asarray(control.parent) >= 0, parent >= 0)
    assert ref_bfs.component_tuples(np.asarray(src), parent) > 0


def test_sampler_keeps_head_reservoir_and_last():
    s = Sampler(seed=3, head=2, size=3)
    for k in range(100):
        s.offer(k, np.array([k]))
    kept = s.host()
    assert {0, 1, 99} <= set(kept)
    assert len(kept) == 2 + 3 + 1
    assert all(int(v[0]) == k for k, v in kept.items())
