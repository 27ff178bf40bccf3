"""Per-level fused point read: the engine's read hot loop as one op.

One call answers a key batch against ALL runs of one level — Bloom
probe, fence/page location, and per-run binary search — with the exact
sequential-equivalent I/O accounting the engine has always kept: runs
are visited newest -> oldest, a key resolved by a newer run is not
probed in older ones, and the returned (probes, reads, false-positives)
counters are the integers per-key execution would produce.

Three implementations behind :func:`point_read_level`:

* ``numpy`` (default) — a verbatim factoring of the historical
  ``LSMTree._lookup_batch`` inner loop.  Pure numpy: the subprocess
  execution backend's workers import the engine without jax, so this
  module must stay jax-free unless an opt-in mode is selected.
* ``jnp`` — the dense jax reference (``repro.kernels.point_read.ref``),
  lazily imported; exact splitmix64 under ``jax.enable_x64(True)``.
* ``jnp_limb`` — the same reference with the Bloom hash on uint32 limbs
  (``repro.kernels.point_read.limb``): the TPU-portable arithmetic tier,
  bit-identical to the native uint64 hash.
* ``pallas`` — the fused kernel (``repro.kernels.point_read.kernel``),
  one VMEM pass per key tile per level; interpret mode off-TPU.

All modes return bit-identical results and counters (tested), so the
mode is a pure execution choice — golden ``IOStats`` are preserved.
The switch is process-global (``set_read_kernel`` / ``read_kernel``)
rather than an ``EngineConfig`` field: engine configs stay hashable,
JSON-round-trippable, and jax-free for subprocess workers.
"""

from __future__ import annotations

from contextlib import contextmanager
from typing import Tuple

import numpy as np

from repro import obs

VALID_MODES = ("numpy", "jnp", "jnp_limb", "pallas")

_MODE = "numpy"


def set_read_kernel(mode: str) -> None:
    """Select the point-read implementation for every engine in-process."""
    global _MODE
    if mode not in VALID_MODES:
        raise ValueError(f"unknown read kernel {mode!r}; one of {VALID_MODES}")
    _MODE = mode


def get_read_kernel() -> str:
    return _MODE


@contextmanager
def read_kernel(mode: str):
    """Scoped :func:`set_read_kernel` (tests / benchmarks)."""
    prev = get_read_kernel()
    set_read_kernel(mode)
    try:
        yield
    finally:
        set_read_kernel(prev)


def point_read_level_numpy(lv, sub_keys: np.ndarray
                           ) -> Tuple[np.ndarray, np.ndarray, int, int, int]:
    """(hit, enc, probes, reads, false_positives) for one level.

    ``hit[b]`` is True when key ``b`` was found in this level (including
    tombstones — the caller decides what a tombstone means); ``enc[b]``
    is the encoded value for hit keys.  Counter semantics match per-key
    sequential execution (see module docstring).
    """
    B = len(sub_keys)
    hit = np.zeros(B, bool)
    enc = np.zeros(B, np.int64)
    probes = reads = fps = 0
    pos = lv.pack.probe(sub_keys)                # (R, B)
    live = np.ones(B, bool)                      # unresolved within level
    for r in range(lv.num_runs):                 # newest -> oldest
        n_active = int(live.sum())
        if n_active == 0:
            break
        probes += n_active
        pos_r = pos[r] & live
        n_pos = int(pos_r.sum())
        if n_pos == 0:
            continue
        reads += n_pos                # fence pointer -> one page each
        rkeys, rvals = lv.run_slice(r)
        qk = sub_keys[pos_r]
        loc = np.searchsorted(rkeys, qk)
        inb = loc < len(rkeys)
        eq = np.zeros(n_pos, bool)
        eq[inb] = rkeys[loc[inb]] == qk[inb]
        fps += n_pos - int(eq.sum())
        if eq.any():
            sidx = np.flatnonzero(pos_r)[eq]
            live[sidx] = False
            hit[sidx] = True
            enc[sidx] = rvals[loc[eq]]
    return hit, enc, probes, reads, fps


def point_read_level(lv, sub_keys: np.ndarray
                     ) -> Tuple[np.ndarray, np.ndarray, int, int, int]:
    """Mode-dispatched per-level point read (see module docstring)."""
    if obs.enabled():
        obs.count("kernel.dispatch.point_read." + _MODE)
    if _MODE == "numpy":
        return point_read_level_numpy(lv, sub_keys)
    from repro.kernels.point_read.ops import point_read_level_arrays
    pack = lv.pack
    return point_read_level_arrays(
        sub_keys, lv.keys, lv.vals, np.asarray(lv.starts, np.int64),
        pack.words, np.asarray(pack.n_bits, np.uint64),
        np.asarray(pack.ks, np.int64), lv.min_keys, lv.max_keys, impl=_MODE)
