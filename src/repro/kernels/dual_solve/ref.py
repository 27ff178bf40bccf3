"""Reference jnp implementation of the warm-started dual refinement.

This is the pre-kernel-tier algorithm exactly as the robust tuner ran it
(``core/robust.dual_solve_warm`` before the fused tier): a 3-point local
scan around the carried ``log lam*`` plus a classic golden-section loop
that evaluates *both* interior points at every iteration.  Per call that
is ``n_local + 2 * n_golden + 1`` evaluations of

    g(lam) = rho lam + lam * logsumexp(log w + c / lam)

(16 with the production ``n_local=3, n_golden=6``).  The fused tier
(``ops.dual_solve_warm_fused`` / ``kernel.dual_solve_warm_kernel``)
reuses the bracket endpoints' values across golden iterations and needs
only ``n_local + 2 + n_golden + 1`` (12): same convexity contract, same
second-order-in-bracket-width accuracy (see ``core/robust`` docstring),
strictly fewer g-evaluations.  This module is the accuracy oracle and
the perf baseline the fused paths are gated against.
"""

from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_GR = 0.6180339887498949  # golden ratio conjugate


def scan_offsets(half_width: float, n_local: int) -> Tuple[float, ...]:
    """The local scan's log-lam offsets as static Python floats.

    The fused tiers add these to the carried ``log lam*`` as constants:
    an in-kernel ``jnp.linspace`` lowers to an f32 ``iota``, which Mosaic
    refuses on TPU."""
    return tuple(np.linspace(-half_width, half_width, n_local).tolist())


def first_argmin(vals: Sequence[jnp.ndarray]) -> jnp.ndarray:
    """Index of the first minimum across a short static list of
    same-shape arrays, as int32 selects (no ``argmin`` reduction, which
    the Pallas TPU lowering does not take)."""
    best = vals[0]
    idx = jnp.zeros(jnp.shape(best), jnp.int32)
    for j in range(1, len(vals)):
        better = vals[j] < best
        idx = jnp.where(better, j, idx)
        best = jnp.where(better, vals[j], best)
    return idx


def pick(vals: Sequence[jnp.ndarray], idx: jnp.ndarray) -> jnp.ndarray:
    """``vals[idx]`` elementwise over a short static list, via selects."""
    out = vals[0]
    for j in range(1, len(vals)):
        out = jnp.where(idx == j, vals[j], out)
    return out


def lse(x: jnp.ndarray) -> jnp.ndarray:
    """Stable logsumexp over the last axis, written out primitive-by-
    primitive so the fused jnp path and the Pallas kernel can reproduce
    the exact same op sequence (bit-equivalence is tested)."""
    m = jnp.max(x, axis=-1)
    return m + jnp.log(jnp.sum(jnp.exp(x - m[..., None]), axis=-1))


def g_of_llam(c: jnp.ndarray, logw: jnp.ndarray, rho: jnp.ndarray,
              llam: jnp.ndarray) -> jnp.ndarray:
    """g(exp(llam)) for one lane: c, logw (n,); rho, llam scalars."""
    lam = jnp.maximum(jnp.exp(llam), 1e-12)
    # lam * (rho + s), not rho * lam + lam * s: no multiply feeds an add,
    # so no fused multiply-add can round the two tiers apart.
    return lam * (rho + lse(logw + c / lam))


def dual_solve_warm_ref(c: jnp.ndarray, w: jnp.ndarray, rho, llam,
                        half_width: float = 0.8, n_local: int = 3,
                        n_golden: int = 6):
    """One warm-started dual refinement; returns ``(value, new log lam*)``.

    Single-lane reference: scans ``n_local`` points on ``llam +-
    half_width`` (log-lam), brackets the convex minimum, golden-refines
    with two g-evaluations per iteration, and re-evaluates g at the
    clipped bracket midpoint.
    """
    c = jnp.asarray(c)
    logw = jnp.log(jnp.asarray(w))
    llam = jax.lax.stop_gradient(llam)

    offs = jnp.linspace(-half_width, half_width, n_local)
    lls = llam + offs
    vals = jax.vmap(lambda ll: g_of_llam(c, logw, rho, ll))(lls)
    i = jnp.argmin(vals)
    llo = lls[jnp.maximum(i - 1, 0)]
    lhi = lls[jnp.minimum(i + 1, n_local - 1)]

    def body(_, bounds):
        llo, lhi = bounds
        a = lhi - _GR * (lhi - llo)
        b = llo + _GR * (lhi - llo)
        fa = g_of_llam(c, logw, rho, a)
        fb = g_of_llam(c, logw, rho, b)
        smaller = fa < fb
        return jnp.where(smaller, llo, a), jnp.where(smaller, b, lhi)

    llo, lhi = jax.lax.fori_loop(0, n_golden, body, (llo, lhi))
    lspan = jnp.log(jnp.maximum(jnp.max(c) - jnp.min(c), 1e-9))
    llam_new = jax.lax.stop_gradient(
        jnp.clip(0.5 * (llo + lhi), lspan - 16.0, lspan + 16.0))
    val = jnp.where(rho <= 0.0, jnp.dot(jnp.asarray(w), c),
                    g_of_llam(c, logw, rho, llam_new))
    return val, llam_new
