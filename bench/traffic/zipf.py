"""YCSB's key choosers, on the host.

``requestdistribution=zipfian`` in YCSB's ``CoreWorkload`` draws each key
with ``ScrambledZipfianGenerator(0, recordcount)`` (no inserts, so no
room is kept for new keys).  That generator draws a rank from
``ZipfianGenerator(0, ITEM_COUNT, 0.99, ZETAN)``, Gray et al.'s closed form
("Quickly generating billion-record synthetic databases", SIGMOD 1994) of
one uniform ``u`` over ``items = ITEM_COUNT + 1`` ranks:

    uz = u * ZETAN
    r  = 0                                      if uz < 1
    r  = 1                                      if uz < 1 + 0.5^theta
    r  = (long) (items * (eta * u - eta + 1)^alpha)   otherwise

with ``alpha = 1 / (1 - theta)`` and
``eta = (1 - (2 / items)^(1 - theta)) / (1 - zeta(2) / ZETAN)``, and folds
it onto the keys as ``fnvhash64(r) % (recordcount + 1)``.  ``CoreWorkload``
draws again while the key is past the last one loaded, ``recordcount - 1``.
The hottest key so takes about ``1 / ZETAN`` = 3.8% of the draws, and the
ranks past ``recordcount`` spread over the keys nearly uniformly.
"""

from __future__ import annotations

import numpy as np

#: YCSB's default zipfian constant
YCSB_THETA = 0.99
#: ``ScrambledZipfianGenerator.ITEM_COUNT``: the ranks drawn from
ITEM_COUNT = 10_000_000_000
#: ``ScrambledZipfianGenerator.ZETAN``: zeta(ITEM_COUNT, 0.99), precomputed
ZETAN = 26.46902820178302
#: ``Utils.fnvhash64``'s constants (64-bit FNV-1a)
FNV_OFFSET_BASIS_64 = 0xCBF29CE484222325
FNV_PRIME_64 = 1099511628211


def zeta(n: int, theta: float) -> float:
    """``sum_{i=1..n} i^-theta``: summed exactly up to 2^16 terms, the rest by
    Euler-Maclaurin (error far below 1e-12 of the sum)."""
    head = min(n, 1 << 16)
    i = np.arange(1, head + 1, dtype=np.float64)
    s = float(np.sum(i ** -theta))
    if n == head:
        return s
    a, b = float(head), float(n)

    def f(x):
        return x ** -theta

    def df(x):
        return -theta * x ** (-theta - 1)

    def d3f(x):
        return -theta * (theta + 1) * (theta + 2) * x ** (-theta - 3)
    integral = (b ** (1 - theta) - a ** (1 - theta)) / (1 - theta)
    tail = (integral + (f(a) + f(b)) / 2 + (df(b) - df(a)) / 12
            - (d3f(b) - d3f(a)) / 720)
    return s + tail - f(a)


class ZipfianGenerator:
    """YCSB's ``ZipfianGenerator(0, items - 1, theta, zetan)``: ranks in
    ``[0, items)``, from uniforms in [0, 1)."""

    def __init__(self, items: int, theta: float = YCSB_THETA,
                 zetan: float = None):
        self.items, self.theta = int(items), float(theta)
        self.zetan = zeta(self.items, theta) if zetan is None else zetan
        self.alpha = 1.0 / (1.0 - theta)
        self.eta = ((1 - (2.0 / self.items) ** (1 - theta))
                    / (1 - zeta(2, theta) / self.zetan))

    def ranks(self, u: np.ndarray) -> np.ndarray:
        u = np.asarray(u, np.float64)
        uz = u * self.zetan
        r = np.floor(self.items * (self.eta * u - self.eta + 1) ** self.alpha)
        r = np.where(uz < 1 + 0.5 ** self.theta, 1, r)
        return np.where(uz < 1, 0, r).astype(np.int64)

    def probabilities(self, top: int) -> np.ndarray:
        """Probability the closed form gives each of the ranks 0..top-1."""
        out = np.zeros(top)
        out[0] = 1.0 / self.zetan
        if top > 1:
            out[1] = 0.5 ** self.theta / self.zetan
        u_lo = (1 + 0.5 ** self.theta) / self.zetan   # the closed form's start
        for r in range(2, top):
            # floor(items * (eta*u - eta + 1)^alpha) == r  <=>  u in [lo, hi)
            lo = ((r / self.items) ** (1 / self.alpha) - 1 + self.eta) / self.eta
            hi = (((r + 1) / self.items) ** (1 / self.alpha) - 1
                  + self.eta) / self.eta
            out[r] = max(0.0, min(hi, 1.0) - max(lo, u_lo))
        return out


def fnvhash64(x: np.ndarray) -> np.ndarray:
    """``Utils.fnvhash64``: FNV-1a over the 8 low-first bytes of each
    non-negative value, then Java's ``Math.abs`` of the signed result."""
    v = np.asarray(x, np.int64).astype(np.uint64)
    h = np.full(v.shape, FNV_OFFSET_BASIS_64, np.uint64)
    for _ in range(8):
        h ^= v & np.uint64(0xFF)
        h *= np.uint64(FNV_PRIME_64)            # modulo 2^64, as Java's long
        v >>= np.uint64(8)
    h = h.view(np.int64)
    return np.where(h < 0, -h, h)               # abs(Long.MIN_VALUE) stays


def scrambled_zipf_keys(rng: np.random.Generator, size: int,
                        recordcount: int) -> np.ndarray:
    """``size`` keys as ``CoreWorkload``'s zipfian key chooser draws them
    over ``recordcount`` loaded keys, int32."""
    gen = ZipfianGenerator(ITEM_COUNT + 1, YCSB_THETA, ZETAN)
    keys = np.empty(size, np.int64)
    todo = np.arange(size)
    while todo.size:
        k = np.fmod(fnvhash64(gen.ranks(rng.random(todo.size))),
                    recordcount + 1)
        ok = k < recordcount
        keys[todo[ok]] = k[ok]
        todo = todo[~ok]
    return keys.astype(np.int32)


def uniform_keys(rng: np.random.Generator, size: int,
                 recordcount: int) -> np.ndarray:
    """``requestdistribution=uniform``: every loaded key alike, int32."""
    return rng.integers(0, recordcount, size, dtype=np.int32)
