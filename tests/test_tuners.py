"""Tests for the nominal and robust tuners (paper Sections 5-6)."""

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (EXPECTED_WORKLOADS, DesignSpace, LSMSystem,
                        cost_vector, expected_cost, kl_divergence,
                        primal_worst_case, robust_cost, tune_nominal,
                        tune_nominal_slsqp, tune_robust, worst_case_workload)
from repro.core.robust import _g_of_lam, dual_objective_explicit

SYS = LSMSystem()
W7 = EXPECTED_WORKLOADS[7]
W11 = EXPECTED_WORKLOADS[11]


# ---------------------------------------------------------------------------
# Robust dual machinery (independent of the LSM cost model)
# ---------------------------------------------------------------------------

cost_strat = st.lists(st.floats(min_value=0.01, max_value=100.0), min_size=4,
                      max_size=4)
w_strat = st.lists(st.floats(min_value=0.01, max_value=1.0), min_size=4,
                   max_size=4)
rho_strat = st.floats(min_value=0.01, max_value=4.0)


@settings(max_examples=50, deadline=None)
@given(c=cost_strat, w=w_strat, rho=rho_strat)
def test_duality_gap_zero(c, w, rho):
    """Lemma 1 / Ben-Tal et al.: dual value == exact primal worst case."""
    c = jnp.asarray(c, jnp.float32)
    w = jnp.asarray(w, jnp.float32)
    w = w / w.sum()
    dual = float(robust_cost(c, w, rho))
    w_hat = worst_case_workload(c, w, rho)
    primal = float(jnp.dot(w_hat, c))
    assert dual == pytest.approx(primal, rel=2e-3, abs=1e-4)


@settings(max_examples=50, deadline=None)
@given(c=cost_strat, w=w_strat, rho=rho_strat)
def test_worst_case_in_uncertainty_region(c, w, rho):
    """Eq. 12: the maximizer lies in U^rho_w (KL <= rho, simplex)."""
    c = jnp.asarray(c, jnp.float32)
    w = jnp.asarray(w, jnp.float32)
    w = w / w.sum()
    w_hat = worst_case_workload(c, w, rho)
    assert float(jnp.sum(w_hat)) == pytest.approx(1.0, abs=1e-5)
    assert float(kl_divergence(w_hat, w)) <= rho * (1 + 1e-3) + 1e-5
    # And it is at least as adversarial as the nominal workload.
    assert float(jnp.dot(w_hat, c)) >= float(jnp.dot(w, c)) - 1e-5


def test_worst_case_ties_split_in_proportion_to_w():
    """Tied maximal costs, ball wide enough for the point-mass limit: the
    worst case puts all mass on the ties in proportion to w (KL = log 2
    here), not evenly (KL 0.752 > rho)."""
    c = jnp.asarray([1.0, 1.0, 0.5, 0.5], jnp.float32)
    w = jnp.asarray([1.0, 0.5, 0.5, 1.0], jnp.float32) / 3.0
    w_hat = worst_case_workload(c, w, 0.75)
    np.testing.assert_allclose(np.asarray(w_hat), [2 / 3, 1 / 3, 0, 0],
                               atol=1e-6)
    assert float(kl_divergence(w_hat, w)) <= 0.75


@settings(max_examples=30, deadline=None)
@given(c=cost_strat, w=w_strat, rho=rho_strat)
def test_eta_elimination_exact(c, w, rho):
    """The closed-form eta* = lam log E[e^{c/lam}] makes Eq. 16 == the
    entropic-risk form used by robust_cost."""
    c64 = np.asarray(c, np.float64)
    w64 = np.asarray(w, np.float64)
    w64 = w64 / w64.sum()
    for lam in (0.5, 1.0, 10.0):
        # float64 host evaluation of Eq. 16 verbatim (the f32 device version
        # overflows exp() at small lam -- which is *why* robust_cost uses the
        # eta-eliminated logsumexp form).
        m = (c64 / lam).max()
        eta_star = lam * (m + np.log(np.sum(w64 * np.exp(c64 / lam - m))))
        s = (c64 - eta_star) / lam
        explicit = eta_star + rho * lam + lam * np.sum(w64 * (np.exp(s) - 1.0))
        eliminated = float(_g_of_lam(jnp.asarray(c64, jnp.float32),
                                     jnp.asarray(w64, jnp.float32), rho,
                                     jnp.asarray(lam, jnp.float32)))
        assert explicit == pytest.approx(eliminated, rel=1e-3, abs=1e-3)


@settings(max_examples=30, deadline=None)
@given(c=cost_strat, w=w_strat)
def test_rho_zero_is_nominal(c, w):
    c = jnp.asarray(c, jnp.float32)
    w = jnp.asarray(w, jnp.float32)
    w = w / w.sum()
    assert float(robust_cost(c, w, 0.0)) == pytest.approx(
        float(jnp.dot(w, c)), rel=1e-5)


@settings(max_examples=30, deadline=None)
@given(c=cost_strat, w=w_strat, rho=rho_strat)
def test_robust_cost_monotone_in_rho(c, w, rho):
    c = jnp.asarray(c, jnp.float32)
    w = jnp.asarray(w, jnp.float32)
    w = w / w.sum()
    a = float(robust_cost(c, w, rho))
    b = float(robust_cost(c, w, rho + 0.5))
    assert b >= a - 1e-4
    # And bounded by the max cost (point mass is the worst possible).
    assert b <= float(jnp.max(c)) * (1 + 1e-4) + 1e-5


# ---------------------------------------------------------------------------
# End-to-end tuner behaviour on the paper's workloads
# ---------------------------------------------------------------------------

def test_nominal_matches_paper_structure_w7():
    """Paper Table 5 w7 (49% z0, 49% w): nominal = tiering, T ~ 8."""
    r = tune_nominal(W7, SYS, seed=0)
    K = np.asarray(r.phi.K)
    T = float(r.phi.T)
    assert np.allclose(K[:2], T - 1.0), "w7 nominal should be tiering"
    assert 4 <= T <= 20


def test_nominal_matches_paper_structure_w11():
    """Paper Table 5 w11 (read-heavy): nominal = leveling, large T."""
    r = tune_nominal(W11, SYS, seed=0)
    K = np.asarray(r.phi.K)
    assert np.allclose(K[:2], 1.0), "w11 nominal should be leveling"
    assert float(r.phi.T) >= 20


def test_robust_zero_rho_equals_nominal():
    """Section 8: ENDURE matches nominal when uncertainty is zero."""
    rn = tune_nominal(W11, SYS, seed=0)
    rr = tune_robust(W11, 0.0, SYS, seed=0)
    assert rr.cost == pytest.approx(rn.cost, rel=0.02)


def test_robust_prefers_leveling_and_smaller_T():
    """Section 8.3 / Table 5: robust w11 tunings shrink T and choose
    leveling; Section 11: 'leveling is more robust than tiering'."""
    rn = tune_nominal(W11, SYS, seed=0)
    rr = tune_robust(W11, 1.0, SYS, seed=0)
    assert float(rr.phi.T) < float(rn.phi.T)
    assert np.allclose(np.asarray(rr.phi.K)[:2], 1.0)


def test_robust_improves_worst_case():
    """The whole point: Phi_R beats Phi_N on the worst case at radius rho."""
    rho = 1.0
    rn = tune_nominal(W7, SYS, seed=0)
    rr = tune_robust(W7, rho, SYS, seed=0)
    c_n = cost_vector(rn.phi, SYS)
    c_r = cost_vector(rr.phi, SYS)
    w = jnp.asarray(W7, jnp.float32)
    assert float(robust_cost(c_r, w, rho)) <= float(
        robust_cost(c_n, w, rho)) * (1 + 1e-3)


def test_flexible_designs_no_worse_nominal():
    """Fig. 4: K-LSM >= Fluid >= classic at their own nominal optima."""
    r_classic = tune_nominal(W7, SYS, DesignSpace.CLASSIC, seed=0)
    r_fluid = tune_nominal(W7, SYS, DesignSpace.FLUID, seed=0)
    r_klsm = tune_nominal(W7, SYS, DesignSpace.KLSM, n_starts=128, seed=0)
    assert r_fluid.cost <= r_classic.cost * 1.02
    assert r_klsm.cost <= r_fluid.cost * 1.05  # equal-or-better up to solver noise


@pytest.mark.slow
def test_slsqp_parity_nominal():
    """SciPy SLSQP (paper solver) agrees with the JAX tuner within a few %."""
    r_jax = tune_nominal(W11, SYS, seed=0)
    r_slsqp = tune_nominal_slsqp(W11, SYS, seed=0)
    assert r_slsqp.cost == pytest.approx(r_jax.cost, rel=0.05)
