"""Workloads, KL-divergence uncertainty regions, and the rho heuristics.

A workload is a probability vector ``w = (z0, z1, q, w_frac)`` over the four
query classes (paper Section 3).  The uncertainty region (Eq. 12) is

    U^rho_w = { w' >= 0 : sum w' = 1, I_KL(w', w) <= rho }.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

QUERY_CLASSES = ("z0", "z1", "q", "w")
DIM = 4


def normalize(w: jnp.ndarray) -> jnp.ndarray:
    w = jnp.maximum(w, 0.0)
    return w / jnp.sum(w, axis=-1, keepdims=True)


def kl_divergence(p: jnp.ndarray, q: jnp.ndarray) -> jnp.ndarray:
    """I_KL(p, q) = sum_i p_i log(p_i / q_i); 0 log 0 := 0 (Definition 1)."""
    p = jnp.asarray(p)
    q = jnp.asarray(q)
    ratio = jnp.where(p > 0, p / jnp.maximum(q, 1e-30), 1.0)
    return jnp.sum(jnp.where(p > 0, p * jnp.log(ratio), 0.0), axis=-1)


def worst_case_workload(c: jnp.ndarray, w: jnp.ndarray, rho: float,
                        iters: int = 80) -> jnp.ndarray:
    """Exact inner maximizer of Eq. 13: argmax_{w' in U^rho_w} w'^T c.

    The maximizer is the exponential tilt  w'_i ∝ w_i exp(c_i / lam)  with the
    temperature ``lam >= 0`` chosen so that I_KL(w', w) = rho (or lam -> 0 when
    even the point mass on argmax c is inside the ball).  Solved by bisection;
    fully differentiable in ``c`` via the closed form at fixed lam.
    """
    c = jnp.asarray(c, jnp.float64) if jax.config.jax_enable_x64 else jnp.asarray(c)
    w = normalize(jnp.asarray(w, c.dtype))
    span = jnp.maximum(jnp.max(c) - jnp.min(c), 1e-12)

    def tilt(lam):
        # Shift by max(c) before dividing: at tiny lam, c / lam alone is so
        # large that log(w) drops below its f32 ulp, and tied maxima would
        # split evenly instead of in proportion to w.
        logits = jnp.log(w) + (c - jnp.max(c)) / jnp.maximum(lam, 1e-12)
        return jax.nn.softmax(logits)

    # Degenerate cases: rho <= 0 -> w itself; flat costs -> w itself.
    def kl_at(lam):
        return kl_divergence(tilt(lam), w)

    # KL(tilt(lam), w) is decreasing in lam; find lam with KL = rho.
    lo = span * 1e-9
    hi = span * 1e9

    def body(_, bounds):
        lo, hi = bounds
        mid = jnp.sqrt(lo * hi)  # geometric bisection over many decades
        too_spread = kl_at(mid) > rho
        return jnp.where(too_spread, mid, lo), jnp.where(too_spread, hi, mid)

    lo, hi = jax.lax.fori_loop(0, iters, body, (lo, hi))
    lam = jnp.sqrt(lo * hi)
    w_hat = tilt(lam)
    # If even the most adversarial tilt stays within rho (max KL is bounded by
    # -log w_argmax), return the point-mass-limit tilt at tiny lam.
    w_lim = tilt(jnp.asarray(span * 1e-9, c.dtype))
    w_hat = jnp.where(kl_at(span * 1e-9) <= rho, w_lim, w_hat)
    return jnp.where(rho <= 0.0, w, jnp.where(span < 1e-12, w, w_hat))


def rho_from_history(workloads: np.ndarray) -> float:
    """Algorithm 1: rho = max_i I_KL(w_i, w_bar) over historical workloads."""
    W = np.asarray(workloads, dtype=np.float64)
    w_bar = W.mean(axis=0)
    kls = np.array([float(kl_divergence(w, w_bar)) for w in W])
    return float(kls.max())


def rho_from_pair(expected: np.ndarray, off_period: np.ndarray) -> float:
    """DBA heuristic: KL between an expected and an off-period workload."""
    return float(kl_divergence(np.asarray(off_period), np.asarray(expected)))


def rho_from_ranges(lo: np.ndarray, hi: np.ndarray, n_samples: int = 4096,
                    seed: int = 0) -> float:
    """DBA heuristic: sample workloads within per-class ranges, apply Alg. 1."""
    rng = np.random.default_rng(seed)
    lo = np.asarray(lo, np.float64)
    hi = np.asarray(hi, np.float64)
    samples = rng.uniform(lo, hi, size=(n_samples, DIM))
    samples = samples / samples.sum(axis=1, keepdims=True)
    return rho_from_history(samples)
