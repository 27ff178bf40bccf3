"""jit'd wrapper: model-facing chunked WKV (Pallas on TPU, interpret on CPU)."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .._compat import interpret_default
from .kernel import rwkv6_kernel


@partial(jax.jit, static_argnames=("chunk",))
def rwkv6_chunked(r, k, v, logw, u, chunk: int = 32):
    """r/k/v/logw: (B, S, H, n); u: (H, n) -> (y (B,S,H,n) f32,
    final state (B,H,n,n) f32). Drop-in for models.rwkv.wkv_chunked."""
    B, S, H, n = r.shape
    to_flat = lambda t: t.transpose(0, 2, 1, 3).reshape(B * H, S, n)
    u_flat = jnp.tile(u, (B, 1))
    y, state = rwkv6_kernel(to_flat(r), to_flat(k), to_flat(v),
                            to_flat(logw), u_flat, chunk=chunk,
                            interpret=interpret_default())
    y = y.reshape(B, H, S, n).transpose(0, 2, 1, 3)
    return y, state.reshape(B, H, n, n)
