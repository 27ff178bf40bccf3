"""Plain reference of the key-value contents: one value per record.

The store is loaded with ``lsm.populate``, which writes ``key % 997`` for
every key; every acknowledged update writes the value 1.  A read of a
record must return its newest acknowledged value."""

from __future__ import annotations

from typing import List, Optional

import numpy as np


class KVModel:
    def __init__(self, keys: np.ndarray):
        self.keys = np.asarray(keys, np.uint64)
        self.values = (self.keys % np.uint64(997)).astype(np.int64)
        self.written = np.zeros(len(self.keys), bool)

    def update(self, records: np.ndarray, value: int) -> None:
        self.values[records] = value
        self.written[records] = True

    def sample(self, rng: np.random.Generator, n: int) -> np.ndarray:
        """``n`` records drawn from ``rng``: half of them among the records
        written since loading (as many as there are), the rest among all."""
        written = np.flatnonzero(self.written)
        k = min(n // 2, len(written))
        a = rng.choice(written, size=k, replace=False) if k else \
            np.zeros(0, np.int64)
        b = rng.choice(len(self.keys), size=n - k, replace=False)
        return np.concatenate([a, b]).astype(np.int64)

    def wrong(self, records: np.ndarray, got: List[Optional[int]]) -> int:
        """How many reads of ``records`` did not return the model's value."""
        want = self.values[records]
        return int(sum(g is None or int(g) != int(w)
                       for g, w in zip(got, want.tolist())))
