"""Pallas lane-tiled kernel for the cached-point warm dual solve.

One grid step owns a tile of ``LANE_TILE`` independent lanes (tuning
starts x problems), laid out lanes-last so the cost matrix tile is
``(n, 128)`` — the n-axis reductions (logsumexp over the 4 workload
components) are sublane reductions and every golden iteration is a
fully vectorized VPU pass over the tile.  The entire solve — local
scan, bracket pick, ``n_golden`` cached-point golden iterations, final
re-evaluation — runs on-chip per tile; nothing round-trips to HBM
between g-evaluations.

The op sequence mirrors ``ops.dual_solve_warm_fused`` primitive for
primitive (same hand-written logsumexp from ``ref.lse``, same
where-selects), so interpret-mode outputs are bit-identical to the
vmapped fused path — tested in ``tests/test_kernels.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .._compat import compiler_params, interpret_default
from .ref import _GR, first_argmin, pick, scan_offsets

LANE_TILE = 128


def _dual_solve_tile(c_ref, w_ref, rho_ref, llam_ref, val_ref, lnew_ref, *,
                     half_width: float, n_local: int, n_golden: int):
    C = c_ref[...]            # (n, T)
    W = w_ref[...]            # (n, T)
    rho = rho_ref[...]        # (1, T)
    llam = llam_ref[...]      # (1, T)
    logW = jnp.log(W)

    def g(ll):                # (1, T) -> (1, T)
        lam = jnp.maximum(jnp.exp(ll), 1e-12)
        x = logW + C / lam
        m = jnp.max(x, axis=0, keepdims=True)
        s = m + jnp.log(jnp.sum(jnp.exp(x - m), axis=0, keepdims=True))
        return lam * (rho + s)

    lls = [llam + o for o in scan_offsets(half_width, n_local)]
    vals = [g(ll) for ll in lls]
    i = first_argmin(vals)
    llo = pick(lls, jnp.maximum(i - 1, 0))
    lhi = pick(lls, jnp.minimum(i + 1, n_local - 1))

    a0 = lhi - _GR * (lhi - llo)
    b0 = llo + _GR * (lhi - llo)
    fa0 = g(a0)
    fb0 = g(b0)

    def body(_, st):
        llo, lhi, a, b, fa, fb = st
        smaller = fa < fb
        nlo = jnp.where(smaller, llo, a)
        nhi = jnp.where(smaller, b, lhi)
        na = jnp.where(smaller, nhi - _GR * (nhi - nlo), b)
        nb = jnp.where(smaller, a, nlo + _GR * (nhi - nlo))
        fnew = g(jnp.where(smaller, na, nb))
        nfa = jnp.where(smaller, fnew, fb)
        nfb = jnp.where(smaller, fa, fnew)
        return (nlo, nhi, na, nb, nfa, nfb)

    llo, lhi, _, _, _, _ = jax.lax.fori_loop(
        0, n_golden, body, (llo, lhi, a0, b0, fa0, fb0))
    span = jnp.max(C, axis=0, keepdims=True) - jnp.min(C, axis=0,
                                                       keepdims=True)
    lspan = jnp.log(jnp.maximum(span, 1e-9))
    lnew = jnp.clip(0.5 * (llo + lhi), lspan - 16.0, lspan + 16.0)
    nominal = jnp.sum(W * C, axis=0, keepdims=True)
    val_ref[...] = jnp.where(rho <= 0.0, nominal, g(lnew))
    lnew_ref[...] = lnew


@functools.partial(jax.jit, static_argnames=("half_width", "n_local",
                                             "n_golden", "interpret"))
def dual_solve_warm_kernel(C, W, rho, llam, half_width: float = 0.8,
                           n_local: int = 3, n_golden: int = 6,
                           interpret: bool | None = None):
    """Batched warm solve: C/W (L, n), rho/llam (L,) -> ((L,), (L,))."""
    if interpret is None:
        interpret = interpret_default()
    L, n = C.shape
    Lp = -(-L // LANE_TILE) * LANE_TILE
    pad = Lp - L
    Ct = jnp.pad(jnp.asarray(C, jnp.float32), ((0, pad), (0, 0))).T
    Wt = jnp.pad(jnp.asarray(W, jnp.float32), ((0, pad), (0, 0)),
                 constant_values=1.0).T
    rho_p = jnp.pad(jnp.asarray(rho, jnp.float32), (0, pad),
                    constant_values=1.0)[None, :]
    llam_p = jnp.pad(jnp.asarray(llam, jnp.float32), (0, pad))[None, :]

    kern = functools.partial(_dual_solve_tile, half_width=half_width,
                             n_local=n_local, n_golden=n_golden)
    val, lnew = pl.pallas_call(
        kern,
        grid=(Lp // LANE_TILE,),
        in_specs=[
            pl.BlockSpec((n, LANE_TILE), lambda i: (0, i)),
            pl.BlockSpec((n, LANE_TILE), lambda i: (0, i)),
            pl.BlockSpec((1, LANE_TILE), lambda i: (0, i)),
            pl.BlockSpec((1, LANE_TILE), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, LANE_TILE), lambda i: (0, i)),
            pl.BlockSpec((1, LANE_TILE), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, Lp), jnp.float32),
            jax.ShapeDtypeStruct((1, Lp), jnp.float32),
        ],
        compiler_params=compiler_params(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(Ct, Wt, rho_p, llam_p)
    return val[0, :L], lnew[0, :L]
