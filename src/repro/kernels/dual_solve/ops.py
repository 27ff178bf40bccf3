"""Dispatch for the warm-started dual solve: reference / fused / Pallas.

Three implementations of one contract (see ``ref.py``):

* ``impl="ref"``   — the pre-fusion algorithm (two g-evaluations per
  golden iteration; 16 per call at production settings).  Accuracy
  oracle and perf baseline.
* ``impl="fused"`` — the production path: a *cached-point* golden
  section that seeds both interior points once and then evaluates only
  the single new point per iteration (12 g-evaluations per call).  The
  bracket shrinks by the same 0.618 factor per iteration, so the value
  error keeps the same second-order-in-bracket-width bound as the
  reference (golden identity: the retained interior point of the old
  bracket *is* an interior point of the new one up to f32 rounding).
  Pure jnp, so it inlines into the tuner's vmap-over-starts scan and
  XLA fuses the whole lane batch.
* ``impl="pallas"``— the same cached-point algorithm as a lane-tiled
  Pallas kernel (``kernel.py``), for batched entry points; bit-equal
  to vmapped ``fused`` (tested).

``impl`` is an explicit (trace-time) argument rather than a module
global: the tuner's jit caches would not observe a global flip.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro import obs

from .ref import (_GR, dual_solve_warm_ref, first_argmin, g_of_llam, pick,
                  scan_offsets)


def dual_solve_warm_fused(c: jnp.ndarray, w: jnp.ndarray, rho, llam,
                          half_width: float = 0.8, n_local: int = 3,
                          n_golden: int = 6):
    """Cached-point warm dual refinement; returns ``(value, new log lam*)``.

    Identical bracket/scan structure to :func:`ref.dual_solve_warm_ref`,
    but the golden loop carries ``(a, b, g(a), g(b))`` so each iteration
    evaluates g once instead of twice: n_local + 2 + n_golden + 1 evals.
    """
    c = jnp.asarray(c)
    w = jnp.asarray(w)
    logw = jnp.log(w)
    llam = jax.lax.stop_gradient(llam)

    lls = [llam + o for o in scan_offsets(half_width, n_local)]
    vals = [g_of_llam(c, logw, rho, ll) for ll in lls]
    i = first_argmin(vals)
    llo = pick(lls, jnp.maximum(i - 1, 0))
    lhi = pick(lls, jnp.minimum(i + 1, n_local - 1))

    a0 = lhi - _GR * (lhi - llo)
    b0 = llo + _GR * (lhi - llo)
    fa0 = g_of_llam(c, logw, rho, a0)
    fb0 = g_of_llam(c, logw, rho, b0)

    def body(_, st):
        llo, lhi, a, b, fa, fb = st
        smaller = fa < fb
        nlo = jnp.where(smaller, llo, a)
        nhi = jnp.where(smaller, b, lhi)
        na = jnp.where(smaller, nhi - _GR * (nhi - nlo), b)
        nb = jnp.where(smaller, a, nlo + _GR * (nhi - nlo))
        fnew = g_of_llam(c, logw, rho, jnp.where(smaller, na, nb))
        nfa = jnp.where(smaller, fnew, fb)
        nfb = jnp.where(smaller, fa, fnew)
        return (nlo, nhi, na, nb, nfa, nfb)

    llo, lhi, _, _, _, _ = jax.lax.fori_loop(
        0, n_golden, body, (llo, lhi, a0, b0, fa0, fb0))
    lspan = jnp.log(jnp.maximum(jnp.max(c) - jnp.min(c), 1e-9))
    llam_new = jax.lax.stop_gradient(
        jnp.clip(0.5 * (llo + lhi), lspan - 16.0, lspan + 16.0))
    val = jnp.where(rho <= 0.0, jnp.sum(w * c),
                    g_of_llam(c, logw, rho, llam_new))
    return val, llam_new


def dual_solve_warm(c, w, rho, llam, half_width: float = 0.8,
                    n_local: int = 3, n_golden: int = 6,
                    impl: str = "fused"):
    """Single-lane dispatch point (the robust tuner calls this)."""
    # Trace-time counter: this body runs when jax (re)traces a caller, so
    # the count is compilations through this tier, not solver invocations.
    obs.count("kernel.dispatch.dual_solve." + impl)
    if impl == "fused":
        return dual_solve_warm_fused(c, w, rho, llam, half_width, n_local,
                                     n_golden)
    if impl == "ref":
        return dual_solve_warm_ref(c, w, rho, llam, half_width, n_local,
                                   n_golden)
    raise ValueError(f"unknown dual_solve impl {impl!r} "
                     "(single-lane: 'fused' or 'ref'; 'pallas' is batched — "
                     "use dual_solve_warm_batch)")


@partial(jax.jit, static_argnames=("half_width", "n_local", "n_golden",
                                   "impl"))
def dual_solve_warm_batch(C, W, rho, llam, half_width: float = 0.8,
                          n_local: int = 3, n_golden: int = 6,
                          impl: str = "fused"):
    """Lane-batched warm solve: C (L, n), W (L, n) or (n,), rho/llam (L,).

    Returns ``(values (L,), new log lam* (L,))``.  ``impl="pallas"``
    routes to the lane-tiled kernel; "fused"/"ref" vmap the single-lane
    implementations.
    """
    # Trace-time counter (see dual_solve_warm): counts jit traces per tier.
    obs.count("kernel.dispatch.dual_solve_batch." + impl)
    C = jnp.asarray(C, jnp.float32)
    rho = jnp.asarray(rho, jnp.float32)
    llam = jnp.asarray(llam, jnp.float32)
    W = jnp.broadcast_to(jnp.asarray(W, jnp.float32), C.shape)
    if impl == "pallas":
        from .kernel import dual_solve_warm_kernel
        return dual_solve_warm_kernel(C, W, rho, llam,
                                      half_width=half_width,
                                      n_local=n_local, n_golden=n_golden)
    fn = dual_solve_warm_fused if impl == "fused" else dual_solve_warm_ref
    return jax.vmap(lambda c, w, r, ll: fn(c, w, r, ll, half_width, n_local,
                                           n_golden))(C, W, rho, llam)
