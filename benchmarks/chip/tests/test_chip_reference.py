"""The plain reference: the cost model agrees with the program's float32
one to rounding, and the KL-robust cost is the value of the worst workload
inside the ball."""

import numpy as np
import pytest

from chip_small import CHIP  # noqa: F401  (puts the benchmark on sys.path)

from chipbench.reference import cost_model as ref

SYS = dict(N=1e10, entry_bits=8192.0, page_bits=32768.0, bits_per_entry=10.0,
           f_a=1.0, f_seq=1.0, s_rq=5e-9, min_buf_bits=67108864.0,
           max_levels=24, max_T=100.0)


def _tunings(rng, n):
    s = ref.System(**SYS)
    T = rng.integers(3, 60, n).astype(np.float64)
    M = rng.uniform(0, s.bits_per_entry * s.N - s.min_buf_bits, n)
    K = np.where(rng.random(n) < 0.5, 1.0, T - 1.0)
    return s, T, M, np.repeat(K[:, None], s.max_levels, axis=1)


def test_cost_vector_matches_program():
    import jax.numpy as jnp
    from repro.core import LSMSystem, Phi, cost_vector
    s, T, M, K = _tunings(np.random.default_rng(0), 32)
    mine = ref.cost_vector(T, M, K, s)
    sysp = LSMSystem(**SYS)
    for i in range(len(T)):
        phi = Phi(T=jnp.float32(T[i]), mfilt_bits=jnp.float32(M[i]),
                  K=jnp.asarray(K[i], jnp.float32))
        theirs = np.asarray(cost_vector(phi, sysp), np.float64)
        np.testing.assert_allclose(mine[i], theirs, rtol=2e-5)


@pytest.mark.parametrize("rho", [0.05, 0.5, 2.0])
def test_robust_cost_is_the_worst_case_in_the_ball(rho):
    rng = np.random.default_rng(1)
    c = rng.uniform(0.5, 20.0, (64, 4))
    w = rng.dirichlet(np.ones(4), 64)
    value = ref.robust_cost(c, w, rho)
    # primal: the exponential tilt w_i exp(c_i / lam) with KL = rho
    for k in range(64):
        j = int(np.argmax(c[k]))
        if rho >= -np.log(w[k][j]):      # the point mass on argmax c fits
            worst = float(c[k][j])
        else:
            lo, hi = -40.0, 40.0
            for _ in range(200):
                mid = 0.5 * (lo + hi)
                z = np.log(w[k]) + (c[k] - c[k].max()) / np.exp(mid)
                q = np.exp(z - z.max())
                q /= q.sum()
                kl = float(np.sum(np.where(q > 0, q * np.log(
                    np.maximum(q, 1e-300) / w[k]), 0.0)))
                lo, hi = (mid, hi) if kl > rho else (lo, mid)
            worst = float(q @ c[k])
        assert value[k] == pytest.approx(worst, rel=1e-6)
        assert value[k] >= float(w[k] @ c[k])


def test_nominal_cost_at_rho_zero():
    rng = np.random.default_rng(2)
    c = rng.uniform(0.5, 20.0, (8, 4))
    w = rng.dirichlet(np.ones(4), 8)
    np.testing.assert_allclose(ref.robust_cost(c, w, 0.0),
                               np.sum(w * c, axis=1))


def test_best_designs_beat_a_dense_grid():
    s = ref.System(**SYS)
    W = np.array([[0.25] * 4, [0.01, 0.49, 0.01, 0.49]])
    R = np.array([0.0, 0.5])
    cost, T, M, K = ref.best_designs(W, R, s)
    np.testing.assert_allclose(
        ref.robust_cost(ref.cost_vector(T, M, K, s), W, R), cost)
    Td, Md, Kd, Cd = ref.design_costs(s, 257)
    for p in range(2):
        dense = ref.robust_cost(Cd, W[p][None, :], R[p]).min()
        assert cost[p] <= dense * (1 + 1e-6)


def test_level_shift_moves_the_level_count():
    s = ref.System(**SYS)
    T, M = np.array([10.0]), np.array([9.0e10])
    K = np.ones((1, s.max_levels))
    x = ref.level_argument(T, M, s)
    base = ref.cost_vector(T, M, K, s)
    up = ref.cost_vector(T, M, K, s, level_shift=np.array([1]))
    # one level more: one more run probed by an empty lookup, more writes
    assert up[0, 2] == pytest.approx(base[0, 2] + 1.0)
    assert up[0, 3] > base[0, 3]
    assert np.ceil(x[0]) >= 1
