"""jit'd wrapper for the blocked-bloom probe kernel."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .._compat import interpret_default
from .kernel import KEY_TILE, bloom_probe_kernel


@partial(jax.jit, static_argnames=("num_hashes",))
def bloom_probe(keys: jnp.ndarray, plane: jnp.ndarray,
                num_hashes: int = 4) -> jnp.ndarray:
    """keys: (N,) uint32 (auto-padded to the 128 tile); plane f32 0/1.
    Returns (N,) bool."""
    N = keys.shape[0]
    pad = (-N) % KEY_TILE
    kp = jnp.pad(keys, (0, pad))
    out = bloom_probe_kernel(kp, plane, num_hashes=num_hashes,
                             interpret=interpret_default())
    return out[:N] > 0.5
