"""Batched tuning engine: single-jit (workload x rho x design) sweeps.

The paper's headline experiments (Figs. 6-10, Table 5) are *sweeps* — every
expected workload crossed with every uncertainty radius rho and candidate
design — yet solving each cell with a separate :func:`tune_nominal` /
:func:`tune_robust` call spends its time in Python dispatch and per-call jit
overhead instead of on the device.  This module flattens the full

    (workload x rho) x multi-start [x CLASSIC branch]

grid into one ``vmap``-over-``vmap`` problem compiled in a single ``jit``:

* :func:`tune_nominal_many`  — NOMINAL TUNING for a batch of workloads;
* :func:`tune_robust_many`   — ROBUST TUNING over a (workloads x rhos) grid.

CLASSIC (= best of {LEVELING, TIERING}) is handled by *folding* both branches
into one padded batch axis: the two designs share the same 2-parameter theta
layout, so each problem simply optimizes ``2 * n_starts`` starts where the
second half carries ``policy = 1.0`` (tiering) through
:func:`repro.core.designs.to_phi_policy`.  Because

    min(min over leveling starts, min over tiering starts)
      = min over the concatenated starts,

with ``argmin`` tie-breaking to the first (leveling) index — exactly the
recursive solver's ``min(cands, ...)`` order — the fold is semantics
preserving, and the shared inits (see ``designs.random_inits_many``) make the
batched results match the sequential tuners seed-for-seed.

Robust inner solve
------------------
The robust objective needs the 1-D convex dual minimum over ``lam`` at every
Adam step.  Instead of re-solving from a cold grid each time, each start
carries ``log lam*`` through the Adam ``lax.scan`` (``minimize_adam_carry``)
and refines it with :func:`repro.core.robust.dual_solve_warm`; only the very
first evaluation per start pays :func:`repro.core.robust.dual_solve_cold`.
See robust.py's module docstring for the warm-start exactness argument.  The
winning start is always re-scored with the full cold-grid ``robust_cost`` on
the integral (rounded) tuning, so reported costs are warm-start independent.

``tune_nominal`` / ``tune_robust`` are thin wrappers over this module with a
single-cell grid, so there is exactly one solver implementation.
"""

from __future__ import annotations

from functools import partial
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs

from . import designs
from .designs import DesignSpace
from .lsm_cost import LSMSystem, Phi, cost_vector, expected_cost
from ._opt import minimize_adam, minimize_adam_carry
from .nominal import TuningResult


def _phi_of(theta, policy, design: DesignSpace, sys: LSMSystem, smooth: bool):
    """theta -> Phi; CLASSIC routes through the traced policy axis."""
    if design is DesignSpace.CLASSIC:
        return designs.to_phi_policy(theta, policy, sys, smooth=smooth)
    return designs.to_phi(theta, design, sys, smooth=smooth)


@partial(jax.jit, static_argnames=("design", "sys", "n_starts", "steps",
                                   "lr", "robust"))
def _solve_many(key, W, rhos, design: DesignSpace, sys: LSMSystem,
                n_starts: int, steps: int, lr: float, robust: bool):
    """The single-jit sweep: W (P, 4) workloads, rhos (P,) radii.

    Returns per-problem arrays: exact cost of the winning start, its CLASSIC
    policy, and the raw + integral-rounded Phi components.  ``key`` is traced
    (a new seed must not recompile the sweep program).
    """
    from .robust import dual_solve_cold, dual_solve_warm, robust_cost

    P = W.shape[0]
    base = designs.random_inits_many(key, P, n_starts, design, sys)
    if design is DesignSpace.CLASSIC:
        # Fold LEVELING/TIERING onto the start axis: (P, 2 * n_starts, p).
        thetas = jnp.concatenate([base, base], axis=1)
        policies = jnp.concatenate([
            jnp.zeros((n_starts,), base.dtype),
            jnp.ones((n_starts,), base.dtype)])
    else:
        thetas = base
        policies = jnp.zeros((n_starts,), base.dtype)

    def solve_problem(w, rho, thetas_p):
        def run_start(theta0, pol):
            if robust:
                def obj(theta, llam):
                    c = cost_vector(_phi_of(theta, pol, design, sys, True),
                                    sys, smooth=True)
                    return dual_solve_warm(c, w, rho, llam)

                c0 = cost_vector(_phi_of(theta0, pol, design, sys, True),
                                 sys, smooth=True)
                _, llam0 = dual_solve_cold(c0, w, rho)
                best_t, _, _ = minimize_adam_carry(obj, theta0, llam0,
                                                   steps=steps, lr=lr)
            else:
                def obj(theta):
                    return expected_cost(
                        w, _phi_of(theta, pol, design, sys, True), sys,
                        smooth=True)

                best_t, _ = minimize_adam(obj, theta0, steps=steps, lr=lr)
            return best_t

        best_ts = jax.vmap(run_start)(thetas_p, policies)

        # Exact re-evaluation (ceil/round, cold-grid dual) before picking a
        # winner: the smooth warm-started objective is only a surrogate.
        def exact_eval(theta, pol):
            phi = _phi_of(theta, pol, design, sys, False).round_integral(sys)
            c = cost_vector(phi, sys, smooth=False)
            if robust:
                return robust_cost(c, w, rho)
            return jnp.dot(w, c)

        exact = jax.vmap(exact_eval)(best_ts, policies)
        i = jnp.argmin(jnp.where(jnp.isfinite(exact), exact, jnp.inf))
        t_win, pol_win = best_ts[i], policies[i]
        raw = _phi_of(t_win, pol_win, design, sys, False)
        phi = raw.round_integral(sys)
        return (exact[i], pol_win, raw.T, raw.mfilt_bits, raw.K, phi.T, phi.K)

    return jax.vmap(solve_problem)(W, rhos, thetas)


def _build_results(out, design: DesignSpace,
                   sys: LSMSystem) -> List[TuningResult]:
    """Device outputs -> TuningResults, numpy-only (no per-cell dispatches)."""
    cost, pol, T_raw, mfilt, K_raw, T_int, K_int = [
        np.asarray(x) for x in jax.device_get(out)]
    results = []
    for p in range(cost.shape[0]):
        if design is DesignSpace.CLASSIC:
            d = DesignSpace.TIERING if pol[p] > 0.5 else DesignSpace.LEVELING
        else:
            d = design
        raw_phi = Phi(T=T_raw[p], mfilt_bits=mfilt[p], K=K_raw[p])
        phi = Phi(T=T_int[p], mfilt_bits=mfilt[p], K=K_int[p])
        results.append(TuningResult(phi=phi, cost=float(cost[p]), design=d,
                                    raw_phi=raw_phi, solver="jax"))
    return results


def _grid_span(W, n_starts: int, steps: int):
    """The ``tune.grid`` span of one ``_solve_many`` grid of ``W``'s rows.

    Each caller enters it around its own ``_solve_many`` call rather than
    calling the grid through a shared helper function: the extra Python
    frame made the grid's first trace in a process 0.5-0.6 s slower on a
    TPU v5e host (same jaxpr, same programs)."""
    return obs.span("tune.grid", problems=int(W.shape[0]), starts=n_starts,
                    steps=steps)


def solve_grid(key, W_flat, rho_flat, design: DesignSpace, sys: LSMSystem,
               n_starts: int, steps: int, lr: float, robust: bool):
    """Flat-grid entry point for execution backends (repro.api.backends).

    Identical jit program to the ``tune_*_many`` wrappers, but the caller
    controls the placement of ``W_flat`` (P, 4) / ``rho_flat`` (P,) — e.g.
    device_put with a NamedSharding over the problem axis shards the vmap
    lanes across a mesh.  Pair with :func:`build_results` on the output."""
    with _grid_span(W_flat, n_starts, steps):
        return _solve_many(key, W_flat, rho_flat, design, sys, n_starts,
                           steps, lr, robust=robust)


def build_results(out, design: DesignSpace, sys: LSMSystem
                  ) -> List[TuningResult]:
    """Public counterpart of the device-output -> TuningResult conversion."""
    return _build_results(out, design, sys)


def _as_workload_matrix(workloads) -> jnp.ndarray:
    W = np.atleast_2d(np.asarray(workloads, np.float32))
    if W.ndim != 2 or W.shape[1] != 4:
        raise ValueError(f"workloads must be (P, 4), got {W.shape}")
    return jnp.asarray(W)


def tune_nominal_many(workloads, sys: LSMSystem,
                      design: DesignSpace = DesignSpace.CLASSIC,
                      n_starts: int = 64, steps: int = 250, lr: float = 0.25,
                      seed: int = 0) -> List[TuningResult]:
    """Solve NOMINAL TUNING for every workload in one device dispatch.

    Equivalent to ``[tune_nominal(w, sys, design, ...) for w in workloads]``
    (same seeds, same multi-start inits, same winner selection) but compiled
    as a single jit over the whole batch.
    """
    W = _as_workload_matrix(workloads)
    rhos = jnp.zeros((W.shape[0],), jnp.float32)
    with _grid_span(W, n_starts, steps):
        out = _solve_many(jax.random.PRNGKey(seed), W, rhos, design, sys,
                          n_starts, steps, lr, robust=False)
        return _build_results(out, design, sys)


def tune_robust_many(workloads, rhos: Sequence[float], sys: LSMSystem,
                     design: DesignSpace = DesignSpace.CLASSIC,
                     n_starts: int = 64, steps: int = 250, lr: float = 0.25,
                     seed: int = 0) -> List[List[TuningResult]]:
    """Solve ROBUST TUNING over the full (workloads x rhos) grid in one jit.

    Returns a nested list indexed ``[workload][rho]``.  Equivalent to a
    sequential ``tune_robust`` double loop with the same seed, at a fraction
    of the wall clock (one dispatch, warm-started dual, folded CLASSIC).
    """
    W = _as_workload_matrix(workloads)
    R = np.asarray(rhos, np.float32).reshape(-1)
    n_w, n_r = W.shape[0], R.shape[0]
    W_flat = jnp.repeat(W, n_r, axis=0)
    rho_flat = jnp.asarray(np.tile(R, n_w))
    with _grid_span(W_flat, n_starts, steps):
        out = _solve_many(jax.random.PRNGKey(seed), W_flat, rho_flat, design,
                          sys, n_starts, steps, lr, robust=True)
        flat = _build_results(out, design, sys)
    return [flat[i * n_r:(i + 1) * n_r] for i in range(n_w)]
