"""Dispatch for the fused per-level point read (jnp ref vs Pallas).

The engine-facing entry point takes the level's host-side numpy arrays
(the ``LevelStore`` arenas + ``BloomPack`` matrices), runs the selected
implementation inside a 64-bit jax scope (the Bloom hash is splitmix64
over uint64 keys), and hands back numpy results plus the three summed
I/O counters in the exact shape ``lsm.read_path`` expects.

Both implementations return bit-identical results and per-key counters
(tested in tests/test_kernels.py); the engine-level golden tests assert
that switching modes leaves query results and ``IOStats`` unchanged.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .ref import as_static, point_read_level_ref


def point_read_level_arrays(sub_keys: np.ndarray, arena_keys: np.ndarray,
                            arena_vals: np.ndarray, starts: np.ndarray,
                            words: np.ndarray, n_bits: np.ndarray,
                            ks: np.ndarray, min_keys: np.ndarray,
                            max_keys: np.ndarray, impl: str = "jnp"
                            ) -> Tuple[np.ndarray, np.ndarray, int, int, int]:
    """(hit, enc, probes, reads, fps) for one level — array-level entry."""
    B = len(sub_keys)
    R = len(starts) - 1
    if B == 0 or R == 0:
        return np.zeros(B, bool), np.zeros(B, np.int64), 0, 0, 0
    st = as_static(starts)
    nb = as_static(n_bits)
    kt = as_static(ks)
    if len(arena_keys) == 0:
        # All runs empty: every key stays live through every run, all
        # Bloom words are zero, so only probes accrue (R per key).
        return (np.zeros(B, bool), np.zeros(B, np.int64), R * B, 0, 0)
    with jax.enable_x64(True):
        keys_j = jnp.asarray(sub_keys, jnp.uint64)
        ak = jnp.asarray(arena_keys, jnp.uint64)
        av = jnp.asarray(arena_vals, jnp.int64)
        wj = jnp.asarray(words, jnp.uint64)
        if impl == "jnp":
            hit, enc, probes, reads, fps = point_read_level_ref(
                keys_j, ak, av, st, wj, nb, kt)
        elif impl == "jnp_limb":
            # the TPU-portable hash tier: splitmix64 on uint32 limbs
            hit, enc, probes, reads, fps = point_read_level_ref(
                keys_j, ak, av, st, wj, nb, kt, use_limb_hash=True)
        elif impl == "pallas":
            from .kernel import point_read_level_kernel
            # Fence keys; empty runs never search, any placeholder works.
            flo = tuple(int(v) for v in np.asarray(min_keys, np.uint64))
            fhi = tuple(int(v) for v in np.asarray(max_keys, np.uint64))
            hit, enc, probes, reads, fps = point_read_level_kernel(
                keys_j, ak, av, wj, st, nb, kt, flo, fhi)
        else:
            raise ValueError(f"unknown point_read impl {impl!r}")
        return (np.asarray(hit), np.asarray(enc),
                int(jnp.sum(probes)), int(jnp.sum(reads)),
                int(jnp.sum(fps)))
