"""Plain reference of the K-LSM cost model and the KL-robust cost.

The paper's Eqs. 1-9 (level count, Monkey false-positive rates, the four
per-query costs) and the worst-case cost over the KL ball (the Ben-Tal dual
of Eq. 16 with eta eliminated), written in numpy over whole arrays of
tunings.  It imports nothing of the program.  Every intermediate is rounded
to ``dtype``: float64 decides ``correct``; ``ml_dtypes.bfloat16`` is the
control, the step below the tuner's float32.

A tuning is ``(T, mfilt_bits, K)`` with ``K`` one run cap per level
(``max_levels`` of them); ``System`` holds the configuration's system
parameters (bits, as in the paper).
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import numpy as np

LN2_SQ = 0.4804530139182014


@dataclasses.dataclass(frozen=True)
class System:
    N: float
    entry_bits: float
    page_bits: float
    bits_per_entry: float
    f_a: float
    f_seq: float
    s_rq: float
    min_buf_bits: float
    max_levels: int
    max_T: float

    @classmethod
    def from_config(cls, system: dict) -> "System":
        return cls(**{f.name: system[f.name]
                      for f in dataclasses.fields(cls)})


def level_argument(T, mfilt, s: System) -> np.ndarray:
    """Eq. 1's ``log_T(N E / m_buf + 1)`` in float64, whose ceiling is the
    number of levels."""
    mbuf = np.maximum(s.bits_per_entry * s.N - np.asarray(mfilt, np.float64),
                      s.min_buf_bits)
    return np.log(s.N * s.entry_bits / mbuf + 1.0) / np.log(
        np.maximum(np.asarray(T, np.float64), 1.0 + 1e-6))


def cost_vector(T, mfilt, K, s: System, dtype=np.float64,
                level_shift=0) -> np.ndarray:
    """(..., 4) costs (z0, z1, q, w) of tunings ``T`` (...), ``mfilt``
    (...), ``K`` (..., max_levels); ``level_shift`` (...) moves the level
    count of Eq. 1 by that many levels."""
    def r(x):
        return np.asarray(x).astype(dtype)

    def c(x):
        return np.asarray(x, dtype)

    T = r(np.maximum(np.asarray(T, np.float64), 1.0 + 1e-6))[..., None]
    mfilt = r(mfilt)[..., None]
    K = r(K)
    i = c(np.arange(1, s.max_levels + 1))
    mbuf_raw = r(c(s.bits_per_entry * s.N) - mfilt)
    mbuf = r(np.maximum(mbuf_raw, c(s.min_buf_bits)))
    logT = r(np.log(T))
    # Eq. 1: L = ceil(log_T(N E / m_buf + 1)), at least 1
    L = r(np.maximum(np.ceil(r(r(np.log(r(c(s.N * s.entry_bits) / mbuf
                                            + c(1.0)))) / logT))
                     + c(np.asarray(level_shift))[..., None], c(1.0)))
    # Eq. 3: Monkey FPRs
    log_f = r(r(r(T / r(T - c(1.0))) * logT) - r(r(L + c(1.0) - i) * logT)
              - r(r(mfilt / c(s.N)) * c(LN2_SQ)))
    f = r(np.clip(r(np.exp(np.minimum(log_f, c(0.0)))), c(1e-30), c(1.0)))
    m = r(i <= L)
    Kc = r(np.clip(K, c(1.0), np.maximum(r(T - c(1.0)), c(1.0))))
    kf = r(r(m * Kc) * f)
    z0 = r(kf.sum(axis=-1, dtype=dtype))
    # Eqs. 5-6
    log_cap = r(r(np.log(r(T - c(1.0))) + r(r(i - c(1.0)) * logT))
                + r(np.log(r(mbuf / c(s.entry_bits)))))
    with np.errstate(over="ignore", invalid="ignore"):
        cap = r(np.where(m > 0, r(np.exp(np.where(m > 0, log_cap,
                                                  c(0.0)))), c(0.0)))
    Nf = r(cap.sum(axis=-1, keepdims=True, dtype=dtype))
    p = r(cap / np.maximum(Nf, c(1.0)))
    above = r(r(np.cumsum(kf, axis=-1, dtype=dtype)) - kf)
    per = r(r(c(1.0) + above) + r(r(c(0.5) * r(Kc - c(1.0))) * f))
    z1 = r(r(p * per).sum(axis=-1, dtype=dtype))
    # Eq. 7
    q = r(c(s.f_seq * s.s_rq * s.N * s.entry_bits / s.page_bits)
          + r(r(m * Kc).sum(axis=-1, dtype=dtype)))
    # Eq. 9
    wper = r(r(r(T - c(1.0)) + Kc) / r(c(2.0) * Kc))
    w = r(c(s.f_seq * (1.0 + s.f_a) * s.entry_bits / s.page_bits)
          * r(r(m * wper).sum(axis=-1, dtype=dtype)))
    return np.stack([z0, z1, q, w], axis=-1)


def robust_cost(cvec, w, rho, dtype=np.float64, n_bisect: int = 28
                ) -> np.ndarray:
    """max over the KL ball of radius ``rho`` around ``w`` of ``w' . c``.

    The maximiser tilts ``w`` to ``w_i exp(c_i / lam)``, with ``lam`` where
    the tilt's KL divergence from ``w`` is ``rho`` (it falls as ``lam``
    grows); the value is the dual's ``rho lam + lam log sum_i w_i
    exp(c_i / lam)`` there.  ``lam`` is found by bisection in log ``lam``
    over 16 decades around the cost span; where even the steepest tilt
    stays inside the ball, the value is ``max_i c_i``.  ``rho <= 0`` is the
    expected cost ``w . c``.  Broadcasts over leading axes."""
    def r(x):
        return np.asarray(x).astype(dtype)

    def c(x):
        return np.asarray(x, dtype)

    cv, w = np.broadcast_arrays(r(cvec), r(w))
    rho = r(np.broadcast_to(np.asarray(rho, np.float64), cv.shape[:-1]))
    cmax = cv.max(axis=-1)
    span = r(np.maximum(r(cmax - cv.min(axis=-1)), c(1e-9)))
    tiny = c(1e-300) if dtype == np.float64 else c(1e-30)
    logw = r(np.log(np.maximum(w, tiny)))

    def tilt(llam):
        """(log-sum-exp, tilted mix, KL of the tilt from w) at lam."""
        lam = r(np.exp(llam))
        z = r(logw + r(r(cv - cmax[..., None]) / lam[..., None]))
        zm = z.max(axis=-1)
        e = r(np.exp(r(z - zm[..., None])))
        se = r(e.sum(axis=-1, dtype=dtype))
        lse = r(zm + r(np.log(se)))
        q = r(e / se[..., None])
        lq = r(r(z - zm[..., None]) - r(np.log(se))[..., None])
        kl = r(r(q * r(lq - logw)).sum(axis=-1, dtype=dtype))
        return lam, lse, kl

    lspan = r(np.log(span))
    lo = r(lspan - c(8.0 * np.log(10.0)))
    hi = r(lspan + c(8.0 * np.log(10.0)))
    for _ in range(n_bisect):
        mid = r(c(0.5) * r(lo + hi))
        _, _, kl = tilt(mid)
        wide = kl > rho                  # lam too small: tilt leaves the ball
        lo, hi = np.where(wide, mid, lo), np.where(wide, hi, mid)
    lam, lse, _ = tilt(r(c(0.5) * r(lo + hi)))
    value = r(r(rho * lam) + r(cmax + r(lam * lse)))
    _, _, kl_steep = tilt(r(lspan - c(8.0 * np.log(10.0))))
    value = np.where(kl_steep <= rho, cmax, value)
    nominal = r(r(w * cv).sum(axis=-1, dtype=dtype))
    return np.where(rho <= 0, nominal, value)


def design_costs(s: System, n_mfilt: int, dtype=np.float64
                 ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Cost vectors of the CLASSIC design grid: every integral size ratio
    the tuner can deploy (3 .. max_T); the run caps it can deploy there
    (leveling, K = 1; tiering, K = T - 1, or T - 2 where the continuous
    ratio was rounded up); and ``n_mfilt`` filter budgets in
    [0, m_total - min_buf].  Returns ``(T, mfilt, K, c)`` over the grid."""
    Ts = np.arange(3, int(np.ceil(s.max_T)) + 1, dtype=np.float64)
    top = max(s.bits_per_entry * s.N - s.min_buf_bits, 0.0)
    mf = np.linspace(0.0, top, n_mfilt)
    T, pol, M = np.meshgrid(Ts, np.array([0.0, 1.0, 2.0]), mf,
                            indexing="ij")
    T, pol, M = T.ravel(), pol.ravel(), M.ravel()
    K = np.where(pol == 1, T - 1.0, np.where(pol == 2, T - 2.0, 1.0))
    K = np.repeat(K[:, None], s.max_levels, axis=1)
    return T, M, K, cost_vector(T, M, K, s, dtype)


def _grid_robust(C, W, rhos, dtype, chunk: int = 512) -> np.ndarray:
    """Robust costs of every design ``C`` (D, 4) for every problem, exact
    where they can be the problem's least and ``inf`` where not: the
    worst case is never below the expected cost, so designs are visited
    in order of expected cost and a problem stops once that passes the
    least robust cost it has found."""
    P, D = W.shape[0], C.shape[0]
    nominal = np.einsum("pk,dk->pd", W[:, 0, :], C)
    order = np.argsort(nominal, axis=1)
    vals = np.full((P, D), np.inf)
    best = np.full(P, np.inf)
    rows = np.arange(P)[:, None]
    for k in range(0, D, chunk):
        sub = order[:, k:k + chunk]
        live = nominal[rows[:, 0], sub[:, 0]] < best
        if not live.any():
            break
        v = np.asarray(robust_cost(C[sub[live]], W[live], rhos[live], dtype),
                       np.float64)
        vals[rows[live], sub[live]] = v
        best[live] = np.minimum(best[live], v.min(axis=1))
    return vals


def best_designs(W, rhos, s: System, n_mfilt: int = 17, n_refine: int = 8,
                 n_golden: int = 32, dtype=np.float64):
    """The reference's own tuning of each problem ``(W[p], rhos[p])``: the
    lowest robust cost it finds over the CLASSIC design grid (a grid over
    the filter budget, then golden-section on the filter budget around the
    ``n_refine`` best grid points of each problem).  Returns
    ``(cost, T, mfilt, K)`` per problem."""
    W = np.asarray(W, np.float64)[:, None, :]
    rhos = np.asarray(rhos, np.float64)[:, None]
    T, M, K, C = design_costs(s, n_mfilt, dtype)
    vals = _grid_robust(C, W, rhos, dtype)                     # (P, D)
    cand = np.argsort(vals, axis=1)[:, :n_refine]              # (P, R)
    step = M.max() / max(n_mfilt - 1, 1)
    lo = np.clip(M[cand] - step, 0.0, M.max())
    hi = np.clip(M[cand] + step, 0.0, M.max())
    Tc, Kc = T[cand], K[cand]

    def f(mf):
        return np.asarray(robust_cost(cost_vector(Tc, mf, Kc, s, dtype), W,
                                      rhos, dtype), np.float64)

    gr = 0.6180339887498949
    for _ in range(n_golden):
        a = hi - gr * (hi - lo)
        b = lo + gr * (hi - lo)
        smaller = f(a) < f(b)
        lo, hi = np.where(smaller, lo, a), np.where(smaller, b, hi)
    mid = 0.5 * (lo + hi)
    fm = f(mid)
    rows = np.arange(len(cand))
    j = np.argmin(fm, axis=1)
    grid_best = np.argmin(vals, axis=1)
    use_grid = vals[rows, grid_best] < fm[rows, j]
    cost = np.where(use_grid, vals[rows, grid_best], fm[rows, j])
    Tb = np.where(use_grid, T[grid_best], Tc[rows, j])
    Mb = np.where(use_grid, M[grid_best], mid[rows, j])
    Kb = np.where(use_grid[:, None], K[grid_best], Kc[rows, j])
    return cost, Tb, Mb, Kb


def best_costs(W, rhos, s: System, **kw) -> np.ndarray:
    """The lowest robust cost the reference finds for each problem."""
    return best_designs(W, rhos, s, **kw)[0]
