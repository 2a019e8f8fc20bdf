"""Classical dual BDCD for ridge regression (arXiv:1612.04003, Alg. 3):

    min_alpha  lam/2 ||X alpha / (lam n)||^2 + 1/(2n) ||alpha + y||^2

with the primal iterate w = -X alpha / (lam n).  Per iteration, with the
block of data-point (column) indices ``I``:
    Y = X[:, I],  A = Y^T Y / (lam n^2) + I / n,
    rhs = (Y^T w - alpha[I] - y[I]) / n,
    da = A^-1 rhs,  alpha[I] += da,  w -= Y da / (lam n).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference import spd_solve, take


def solve(X, y, lam: float, idx, matmul):
    d, n = X.shape
    b = idx.shape[1]
    eye = jnp.eye(b, dtype=jnp.float32)
    lam_n = lam * n

    def body(t, carry):
        w, alpha = carry
        cols = idx[t]
        Y = take(X, cols, 1)
        A = matmul(Y.T, Y) / (lam_n * n) + eye / n
        rhs = (matmul(Y.T, w[:, None])[:, 0] - alpha[cols] - y[cols]) / n
        da = spd_solve(A, rhs)
        return w - matmul(Y, da[:, None])[:, 0] / lam_n, alpha.at[cols].add(da)

    zero = (jnp.zeros((d,), jnp.float32), jnp.zeros((n,), jnp.float32))
    return jax.lax.fori_loop(0, idx.shape[0], body, zero)
