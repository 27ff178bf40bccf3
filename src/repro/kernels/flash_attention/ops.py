"""jit'd wrapper: model-facing flash attention with GQA + 4D layout.

On CPU (this container) the kernel runs in interpret mode; on TPU it lowers
to Mosaic.  The wrapper folds (batch, heads) into the kernel's leading grid
axis and pre-expands GQA kv heads (broadcast; free under TP sharding).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from .._compat import interpret_default
from .kernel import flash_attention_kernel


@partial(jax.jit, static_argnames=("causal", "window", "block_q", "block_kv"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    causal: bool = True, window: Optional[int] = None,
                    block_q: int = 128, block_kv: int = 128) -> jnp.ndarray:
    """q: (B, S, H, d); k/v: (B, S, KV, d). Returns (B, S, H, d)."""
    B, S, H, d = q.shape
    KV = k.shape[2]
    G = H // KV
    if G > 1:
        k = jnp.broadcast_to(k[:, :, :, None, :],
                             (B, k.shape[1], KV, G, d)).reshape(
                                 B, k.shape[1], H, d)
        v = jnp.broadcast_to(v[:, :, :, None, :],
                             (B, v.shape[1], KV, G, d)).reshape(
                                 B, v.shape[1], H, d)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, d)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, k.shape[1], d)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, v.shape[1], d)
    out = flash_attention_kernel(qf, kf, vf, causal=causal, window=window,
                                 block_q=block_q, block_kv=block_kv,
                                 interpret=interpret_default())
    return out.reshape(B, H, S, d).transpose(0, 2, 1, 3)
