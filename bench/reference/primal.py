"""Classical primal BCD for ridge regression (arXiv:1612.04003, Alg. 1):

    min_w  lam/2 ||w||^2 + 1/(2n) ||X^T w - y||^2,   X (d, n)

Per iteration, with the block of row indices ``I``:
    Y = X[I, :],  G = Y Y^T / n + lam I,  r = Y (y - alpha) / n - lam w[I],
    dw = G^-1 r,  w[I] += dw,  alpha += Y^T dw          (alpha == X^T w).
The s-step method the program runs gives the same iterates in exact
arithmetic for any s.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference import spd_solve, take


def solve(X, y, lam: float, idx, matmul):
    d, n = X.shape
    b = idx.shape[1]
    eye = jnp.eye(b, dtype=jnp.float32)

    def body(t, carry):
        w, alpha = carry
        rows = idx[t]
        Y = take(X, rows, 0)
        G = matmul(Y, Y.T) / n + lam * eye
        r = matmul(Y, (y - alpha)[:, None])[:, 0] / n - lam * w[rows]
        dw = spd_solve(G, r)
        return w.at[rows].add(dw), alpha + matmul(Y.T, dw[:, None])[:, 0]

    zero = (jnp.zeros((d,), jnp.float32), jnp.zeros((n,), jnp.float32))
    return jax.lax.fori_loop(0, idx.shape[0], body, zero)
