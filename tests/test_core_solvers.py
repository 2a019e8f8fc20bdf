"""Paper-claim tests for the core solvers (float64).

The central claim (section 3): CA-BCD / CA-BDCD compute the SAME iterates as
BCD / BDCD in exact arithmetic -- communication is restructured, convergence
is untouched.  We verify to ~1e-12 in f64 over multiple (b, s) settings, plus
convergence to the closed-form ridge solution and the CG/TSQR baselines.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (bcd, bdcd, ca_bcd, ca_bdcd, cg_ridge,
                        objective, ridge_exact, sample_blocks, tsqr,
                        tsqr_ridge)
from repro.data import SyntheticSpec, make_regression

from _x64 import x64_mode  # noqa: F401  (autouse fixture)

LAM = 1e-3


@pytest.fixture(scope="module")
def problem():
    jax.config.update("jax_enable_x64", True)  # before data gen
    X, y, _ = make_regression(jax.random.key(0),
                              SyntheticSpec("t", d=60, n=200, cond=1e6))
    return X, y, ridge_exact(X, y, LAM)


def test_cg_matches_direct(problem):
    X, y, w_opt = problem
    w = cg_ridge(X, y, LAM, tol=1e-14, max_iters=500).w
    np.testing.assert_allclose(w, w_opt, rtol=1e-10, atol=1e-12)


def test_tsqr_matches_direct(problem):
    X, y, w_opt = problem
    w = tsqr_ridge(X, y, LAM)
    np.testing.assert_allclose(w, w_opt, rtol=1e-9, atol=1e-11)


def test_tsqr_r_factor(problem):
    X, _, _ = problem
    A = X.T  # 200 x 60 tall
    R = tsqr(A, n_blocks=8)
    np.testing.assert_allclose(R.T @ R, A.T @ A, rtol=1e-10, atol=1e-10)


def test_tsqr_dual_path(problem):
    """d > n branch."""
    X, y, _ = problem
    Xt = X.T  # 200 features x 60 points
    yt = jnp.ones((60,), Xt.dtype)
    w = tsqr_ridge(Xt, yt, LAM)
    np.testing.assert_allclose(w, ridge_exact(Xt, yt, LAM), rtol=1e-9,
                               atol=1e-11)


def test_bcd_converges(problem):
    X, y, w_opt = problem
    res = bcd(X, y, LAM, b=8, iters=600, key=jax.random.key(1), w_ref=w_opt)
    assert float(res.history["sol_err"][-1]) < 1e-8
    # objective decreases overall
    obj = res.history["objective"]
    assert float(obj[-1]) < float(obj[0])


@pytest.mark.parametrize("b,s", [(1, 4), (4, 2), (4, 10), (8, 25)])
def test_ca_bcd_exact_equivalence(problem, b, s):
    """CA-BCD(s) == BCD iterate-for-iterate (same sampled blocks)."""
    X, y, w_opt = problem
    iters = 100
    idx = sample_blocks(jax.random.key(2), X.shape[0], b, iters)
    r_cl = bcd(X, y, LAM, b, iters, None, idx=idx, w_ref=w_opt)
    r_ca = ca_bcd(X, y, LAM, b, s, iters, None, idx=idx, w_ref=w_opt)
    np.testing.assert_allclose(r_ca.w, r_cl.w, rtol=1e-11, atol=1e-13)
    np.testing.assert_allclose(r_ca.history["objective"],
                               r_cl.history["objective"], rtol=1e-9, atol=0)


@pytest.mark.parametrize("b,s", [(1, 4), (8, 5), (16, 25)])
def test_ca_bdcd_exact_equivalence(problem, b, s):
    X, y, w_opt = problem
    iters = 100
    idx = sample_blocks(jax.random.key(3), X.shape[1], b, iters)
    r_cl = bdcd(X, y, LAM, b, iters, None, idx=idx, w_ref=w_opt)
    r_ca = ca_bdcd(X, y, LAM, b, s, iters, None, idx=idx, w_ref=w_opt)
    np.testing.assert_allclose(r_ca.w, r_cl.w, rtol=1e-11, atol=1e-13)
    np.testing.assert_allclose(r_ca.alpha, r_cl.alpha, rtol=1e-11, atol=1e-13)
    for k in ("objective", "residual"):
        np.testing.assert_allclose(r_ca.history[k], r_cl.history[k],
                                   rtol=1e-9, atol=0)


def test_dual_reaches_primal_solution(problem):
    """BDCD's primal iterate w converges to the same ridge solution."""
    X, y, w_opt = problem
    res = bdcd(X, y, LAM, b=16, iters=3000, key=jax.random.key(4), w_ref=w_opt)
    assert float(res.history["sol_err"][-1]) < 1e-6


def test_single_pass_ca_bcd(problem):
    """s == H: one communication round total (paper Fig. 4 's=H=100' setting)."""
    X, y, w_opt = problem
    iters = 64
    idx = sample_blocks(jax.random.key(5), X.shape[0], 4, iters)
    r_cl = bcd(X, y, LAM, 4, iters, None, idx=idx)
    r_ca = ca_bcd(X, y, LAM, 4, iters, iters, None, idx=idx, track_cond=True)
    np.testing.assert_allclose(r_ca.w, r_cl.w, rtol=1e-10, atol=1e-12)
    assert np.all(np.isfinite(r_ca.history["gram_cond"]))


def test_gram_cond_grows_with_s(problem):
    """Fig. 4i: the sb x sb Gram condition number grows with s but stays
    moderate (numerical-stability claim)."""
    X, y, _ = problem
    conds = []
    for s in (2, 8, 32):
        r = ca_bcd(X, y, LAM, 4, s, 64, jax.random.key(6), track_cond=True)
        conds.append(float(np.max(r.history["gram_cond"])))
    assert conds[0] <= conds[1] <= conds[2]
    assert conds[-1] < 1e8  # well-conditioned even at large s


def test_objective_definition(problem):
    X, y, _ = problem
    w = jnp.ones((X.shape[0],), X.dtype)
    n = X.shape[1]
    expected = 0.5 / n * float(jnp.sum((X.T @ w - y) ** 2)) \
        + 0.5 * LAM * float(w @ w)
    assert abs(float(objective(X, w, y, LAM)) - expected) < 1e-10


def test_residual_alpha_invariant(problem):
    """alpha == X^T w is maintained by the residual-form recurrences."""
    X, y, _ = problem
    res = bcd(X, y, LAM, b=8, iters=50, key=jax.random.key(7))
    np.testing.assert_allclose(res.alpha, X.T @ res.w, rtol=1e-10, atol=1e-12)
    res = ca_bcd(X, y, LAM, b=8, s=5, iters=50, key=jax.random.key(7))
    np.testing.assert_allclose(res.alpha, X.T @ res.w, rtol=1e-10, atol=1e-12)
