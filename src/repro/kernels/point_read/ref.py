"""Dense jnp reference for the fused per-level point read.

Same contract as ``lsm.read_path.point_read_level_numpy`` — Bloom probe
+ fence + per-run binary search for a key batch against one level, with
sequential-equivalent accounting — but expressed as fixed-shape dense
ops (masks instead of boolean compaction) so the Pallas kernel can
mirror it op for op.  Counters come back *per key* (their sums are the
engine's integers; the decomposition is what the bit-equivalence tests
compare).

Requires 64-bit mode (``jax.enable_x64(True)``): the Bloom hash
is the engine's exact splitmix64 over uint64 keys.  ``ops.py`` manages
the x64 scope; on TPU hardware uint64 would need limb emulation — this
tier is exercised in interpret mode until then (see docs/kernels.md).
"""

from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp
import numpy as np

_GAMMA = 0x9E3779B97F4A7C15


def splitmix64_jnp(x: jnp.ndarray, seed: int) -> jnp.ndarray:
    """Elementwise splitmix64, bit-identical to ``lsm.bloom.splitmix64``."""
    z = x + jnp.uint64(seed) * jnp.uint64(_GAMMA)
    z = (z ^ (z >> jnp.uint64(30))) * jnp.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> jnp.uint64(27))) * jnp.uint64(0x94D049BB133111EB)
    return z ^ (z >> jnp.uint64(31))


def point_read_level_ref(sub_keys: jnp.ndarray, arena_keys: jnp.ndarray,
                         arena_vals: jnp.ndarray, starts: Tuple[int, ...],
                         words: jnp.ndarray, n_bits: Tuple[int, ...],
                         ks: Tuple[int, ...], use_limb_hash: bool = False
                         ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray,
                                    jnp.ndarray, jnp.ndarray]:
    """Returns (hit, enc, probes_pk, reads_pk, fps_pk), each (B,).

    ``starts``/``n_bits``/``ks`` are static host tuples (the level's
    run layout); ``words`` is the level's packed (R, Wmax) filter
    matrix.  Runs are visited newest -> oldest; per-key counters add 1
    probe per run visited while unresolved, 1 read per Bloom-positive
    visit, 1 false positive per Bloom-positive visit that missed.

    ``use_limb_hash`` routes the Bloom hash through the uint32-limb
    splitmix64 (``limb.py``, bit-identical by construction and by test)
    instead of native uint64 — the TPU-portable arithmetic path.
    """
    B = sub_keys.shape[0]
    R = len(starts) - 1
    kmax = max(ks) if R else 0
    if use_limb_hash:
        from .limb import mod_limbs, split64_jnp, splitmix64_limbs
        xlo, xhi = split64_jnp(sub_keys)
        hs_limb = [splitmix64_limbs(xlo, xhi, j + 1) for j in range(kmax)]
    else:
        hs = [splitmix64_jnp(sub_keys, j + 1) for j in range(kmax)]

    hit = jnp.zeros(B, bool)
    enc = jnp.zeros(B, jnp.int64)
    live = jnp.ones(B, bool)
    probes = jnp.zeros(B, jnp.int64)
    reads = jnp.zeros(B, jnp.int64)
    fps = jnp.zeros(B, jnp.int64)

    for r in range(R):
        probes = probes + live
        bloom_ok = jnp.ones(B, bool)
        for j in range(ks[r]):
            if use_limb_hash:
                hm = mod_limbs(*hs_limb[j], int(n_bits[r])) \
                    .astype(jnp.uint64)
            else:
                hm = hs[j] % jnp.uint64(n_bits[r])
            w = words[r, (hm >> jnp.uint64(6)).astype(jnp.int64)]
            bloom_ok &= ((w >> (hm & jnp.uint64(63)))
                         & jnp.uint64(1)).astype(bool)
        pos = live & bloom_ok
        reads = reads + pos
        s, e = int(starts[r]), int(starts[r + 1])
        if e > s:
            rkeys = arena_keys[s:e]
            loc = jnp.searchsorted(rkeys, sub_keys)
            safe = jnp.minimum(loc, e - s - 1)
            found = pos & (loc < e - s) & (rkeys[safe] == sub_keys)
            venc = arena_vals[s:e][safe]
            hit = hit | found
            enc = jnp.where(found, venc, enc)
            live = live & ~found
        else:
            found = jnp.zeros(B, bool)
        fps = fps + (pos & ~found)
    return hit, enc, probes, reads, fps


def as_static(x) -> Tuple[int, ...]:
    """Host metadata array -> hashable tuple of Python ints."""
    return tuple(int(v) for v in np.asarray(x))
