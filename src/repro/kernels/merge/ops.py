"""Dispatch for the k-way compaction merge (jnp ref vs Pallas).

The engine-facing entry folds a newest-first run list pairwise: each
step is one fixed-shape two-way stable merge (reference scatter form or
the merge-path kernel) followed by a host-side adjacent-duplicate drop
(newest-wins dedup; jax shapes stay static, compaction is host-driven
anyway).  Newest-wins is associative, so the fold is bit-identical to
the legacy global argsort-merge — asserted by the store-level golden
tests.

Runs under ``jax.enable_x64(True)`` (uint64 keys, int64 encoded
values — the engine's exact dtypes).
"""

from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .ref import two_way_merge_ref


def _dedup(keys: np.ndarray, vals: np.ndarray
           ) -> Tuple[np.ndarray, np.ndarray]:
    keep = np.ones(len(keys), bool)
    keep[1:] = keys[1:] != keys[:-1]          # first (newest) wins
    return keys[keep], vals[keep]


def merge_runs_arrays(keys_list: Sequence[np.ndarray],
                      vals_list: Sequence[np.ndarray], impl: str = "jnp"
                      ) -> Tuple[np.ndarray, np.ndarray]:
    """Newest-first k-way merge -> (sorted unique keys, newest vals)."""
    if impl == "pallas":
        from .kernel import two_way_merge_kernel
        two_way = two_way_merge_kernel
    elif impl == "jnp":
        two_way = two_way_merge_ref
    else:
        raise ValueError(f"unknown merge impl {impl!r}")

    acc_k = np.asarray(keys_list[0], np.uint64)
    acc_v = np.asarray(vals_list[0], np.int64)
    with jax.enable_x64(True):
        for k, v in zip(keys_list[1:], vals_list[1:]):
            if len(k) == 0:
                continue
            if len(acc_k) == 0:
                acc_k = np.asarray(k, np.uint64)
                acc_v = np.asarray(v, np.int64)
                continue
            mk, mv = two_way(jnp.asarray(acc_k, jnp.uint64),
                             jnp.asarray(acc_v, jnp.int64),
                             jnp.asarray(k, jnp.uint64),
                             jnp.asarray(v, jnp.int64))
            acc_k, acc_v = _dedup(np.asarray(mk), np.asarray(mv))
    return acc_k, acc_v
