"""Host copy of YCSB's ``ScrambledZipfianGenerator``, vectorized in NumPy.

YCSB core workload C (``workloads/workloadc``: ``readproportion=1``,
``requestdistribution=zipfian``) draws keys from a scrambled zipfian: a
zipfian over ``ITEM_COUNT = 10**10`` items with constant 0.99 and the
precomputed ``ZETAN``, whose draw is then hashed with 64-bit FNV-1a and
folded into the record count.  The hottest record therefore takes
``1 / ZETAN`` (about 3.8%) of all requests, wherever the hash puts it.

This is a copy of YCSB's arithmetic (``ZipfianGenerator.nextLong`` and
``Utils.fnvhash64``), with NumPy's seeded generator in place of
``ThreadLocalRandom``: the same seed gives the same keys.
"""

from __future__ import annotations

import numpy as np

ZIPFIAN_CONSTANT = 0.99
ZETAN = 26.46902820178302  # zeta(ITEM_COUNT, 0.99), as YCSB hard-codes it
ITEM_COUNT = 10_000_000_000
FNV_OFFSET_BASIS_64 = np.uint64(0xCBF29CE484222325)
FNV_PRIME_64 = np.uint64(1099511628211)


def _zeta(n: int, theta: float) -> float:
    return float(sum(1.0 / (i + 1) ** theta for i in range(n)))


def fnvhash64(values: np.ndarray) -> np.ndarray:
    """YCSB's ``Utils.fnvhash64`` over int64 values: FNV-1a over the eight
    low-first octets, then ``Math.abs`` of the signed result."""
    val = values.astype(np.uint64)
    h = np.full(val.shape, FNV_OFFSET_BASIS_64, dtype=np.uint64)
    for _ in range(8):
        h ^= val & np.uint64(0xFF)
        val >>= np.uint64(8)
        h *= FNV_PRIME_64  # wraps mod 2**64, as Java's long multiply does
    return np.abs(h.view(np.int64))


class ScrambledZipfian:
    """Keys in ``[0, record_count)`` under YCSB's scrambled zipfian law."""

    def __init__(self, record_count: int, zipfian_constant: float = ZIPFIAN_CONSTANT):
        if zipfian_constant != ZIPFIAN_CONSTANT:
            raise ValueError("only YCSB's default zipfian constant 0.99 has a known ZETAN")
        self.record_count = int(record_count)
        self.theta = float(zipfian_constant)
        items = ITEM_COUNT + 1  # ZipfianGenerator(0, ITEM_COUNT): max - min + 1
        self.items = items
        self.alpha = 1.0 / (1.0 - self.theta)
        self.zetan = ZETAN
        zeta2 = _zeta(2, self.theta)
        self.eta = (1.0 - (2.0 / items) ** (1.0 - self.theta)) / (1.0 - zeta2 / self.zetan)
        self.second = 1.0 + 0.5**self.theta

    def zipf(self, u: np.ndarray) -> np.ndarray:
        """``ZipfianGenerator.nextLong`` for uniform draws ``u`` in [0, 1)."""
        uz = u * self.zetan
        rank = (self.items * np.power(self.eta * u - self.eta + 1.0, self.alpha)).astype(np.int64)
        rank = np.where(uz < self.second, 1, rank)
        return np.where(uz < 1.0, 0, rank)

    def draw(self, rng: np.random.Generator, n: int) -> np.ndarray:
        """``n`` record numbers as uint32 (``record_count`` <= 2**32)."""
        ranks = self.zipf(rng.random(n))
        return (fnvhash64(ranks) % self.record_count).astype(np.uint32)

    @property
    def hottest_share(self) -> float:
        """Probability of the most requested record: ``1 / ZETAN``."""
        return 1.0 / self.zetan
