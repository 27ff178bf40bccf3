"""YCSB's key chooser and record keys, as in the YCSB core workloads
(Cooper et al., SoCC 2010; ``site.ycsb.generator``).

* ``fnv64``: YCSB's ``Utils.fnvhash64`` (FNV-1 over the 8 low-order bytes,
  then ``Math.abs``), vectorized.
* ``zipf_ranks``: ``ZipfianGenerator.nextLong`` over ``item_count`` items
  with the zipfian constant ``theta`` (Gray et al.'s closed form).
* ``scrambled_zipf``: ``ScrambledZipfianGenerator``: a zipfian rank over
  YCSB's fixed item count of 10^10, hashed onto ``n_records`` records, so
  the popular records are spread over the key space.
* ``record_keys``: the engine key of record ``i``: its FNV hash with the
  top bit cleared (YCSB's ``user<hash>`` keys, as integers).

numpy's ``zipf`` needs an exponent above 1, so YCSB's 0.99 is drawn here.
"""

from __future__ import annotations

import numpy as np

FNV_OFFSET_BASIS_64 = np.uint64(0xCBF29CE484222325)
FNV_PRIME_64 = np.uint64(1099511628211)
ZIPFIAN_CONSTANT = 0.99
#: YCSB's ScrambledZipfianGenerator draws over this many items, with the
#: zeta constant precomputed for theta = 0.99
ITEM_COUNT = 10_000_000_000
ZETAN = 26.46902820178302


def fnv64(values) -> np.ndarray:
    """YCSB ``fnvhash64`` of each value, as non-negative int64."""
    v = np.asarray(values, np.int64).astype(np.uint64)
    h = np.full(v.shape, FNV_OFFSET_BASIS_64, np.uint64)
    with np.errstate(over="ignore"):
        for _ in range(8):
            h = (h ^ (v & np.uint64(0xFF))) * FNV_PRIME_64
            v = v >> np.uint64(8)
    return np.abs(h.view(np.int64))


def zeta(n: int, theta: float) -> float:
    """sum_{i=1}^{n} 1 / i^theta (for small n; YCSB's zeta)."""
    return float(np.sum(1.0 / np.arange(1, n + 1, dtype=np.float64) ** theta))


def zipf_ranks(u: np.ndarray, item_count: int, theta: float,
               zetan: float) -> np.ndarray:
    """Zipfian ranks in [0, item_count) from uniforms ``u`` in [0, 1)."""
    zeta2 = zeta(2, theta)
    alpha = 1.0 / (1.0 - theta)
    eta = (1.0 - (2.0 / item_count) ** (1.0 - theta)) / (1.0 - zeta2 / zetan)
    uz = u * zetan
    tail = (item_count * np.power(eta * u - eta + 1.0, alpha)).astype(np.int64)
    out = np.where(uz < 1.0 + 0.5 ** theta, 1, tail)
    out = np.where(uz < 1.0, 0, out)
    return np.minimum(out, item_count - 1)


def scrambled_zipf(rng: np.random.Generator, n: int, n_records: int,
                   theta: float = ZIPFIAN_CONSTANT) -> np.ndarray:
    """``n`` record indices in [0, n_records), YCSB's scrambled zipfian."""
    if theta != ZIPFIAN_CONSTANT:
        raise ValueError("YCSB precomputes zeta(10^10) for theta 0.99 only")
    ranks = zipf_ranks(rng.random(n), ITEM_COUNT, theta, ZETAN)
    return fnv64(ranks) % np.int64(n_records)


def record_keys(n_records: int) -> np.ndarray:
    """Engine keys of records 0 .. n_records - 1 (distinct, below 2^63)."""
    keys = fnv64(np.arange(n_records)).astype(np.uint64)
    if len(np.unique(keys)) != n_records:
        raise ValueError(f"record keys collide at {n_records} records")
    return keys
