"""The dual's per-iteration metrics: one pass over X per outer step.

The local solve's history needs X^T w_j at every inner iterate w_j.  The
dual evaluates the s_k of them of one outer step in one contraction
(``_BoundDual.metrics_stacked``) after the inner scan, instead of one full
matvec per iterate inside it.  These tests hold it to the one-at-a-time
formula on the same iterates, and read the compiled program to check that
X is read once per outer step for metrics, and never inside the inner scan.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _phases import operand_reads
from repro.core import SolverPlan, s_step_solve
from repro.core.engine import FORMULATIONS
from repro.kernels.gram import ColMajorOperand, RowMajorOperand

DUAL = FORMULATIONS["dual"]


class _OneAtATime:
    """A bound dual without its stacked hook: the engine then replays the
    outer step's updates block by block in the inner scan and evaluates the
    one-iterate ``metrics`` on each iterate, as before the hook existed."""

    def __init__(self, bound):
        self._bound = bound

    def __getattr__(self, name):
        if name == "metrics_stacked":
            raise AttributeError(name)
        return getattr(self._bound, name)


class _OneAtATimeDual:
    name, operand_layout = DUAL.name, DUAL.operand_layout

    def __getattr__(self, name):
        return getattr(DUAL, name)

    def bind(self, *args, **kwargs):
        return _OneAtATime(DUAL.bind(*args, **kwargs))


def _problem(d=96, n=40):
    kx, kw = jax.random.split(jax.random.key(7))
    X = jax.random.normal(kx, (d, n), jnp.float32)
    w_true = jax.random.normal(kw, (d,), jnp.float32)
    return X, X.T @ w_true


@pytest.mark.parametrize("iters", [64, 40])           # 40: a ragged tail of 8
@pytest.mark.parametrize("bind", ["raw", "operand"])
def test_stacked_metrics_match_one_at_a_time(bind, iters):
    X, y = _problem()
    d, n = X.shape
    A = X if bind == "raw" else ColMajorOperand.of(X)
    plan = SolverPlan(b=2, s=16, impl="ref")
    idx = jax.random.randint(jax.random.key(1), (iters, 2), 0, n)
    w_ref = jnp.ones((d,), jnp.float32)
    kw = dict(idx=idx, w_ref=w_ref)
    got = s_step_solve("dual", plan, A, y, 1e-2, iters, **kw)
    ref = s_step_solve(_OneAtATimeDual(), plan, A, y, 1e-2, iters, **kw)
    # The iterates are the engine's own: the hook changes the metrics only.
    np.testing.assert_array_equal(got.w, ref.w)
    np.testing.assert_array_equal(got.alpha, ref.alpha)
    assert set(got.history) == set(ref.history) == {"objective", "residual",
                                                    "sol_err"}
    for k, series in ref.history.items():
        assert got.history[k].shape == (iters,)
        np.testing.assert_allclose(got.history[k], series, rtol=1e-6, atol=0,
                                   err_msg=k)


def _compiled(form, A, y, iters):
    plan = SolverPlan(b=4, s=4, impl="ref")
    dim = A.shape[1] if form == "dual" else A.shape[0]
    idx = jnp.arange(iters * 4, dtype=jnp.int32).reshape(iters, 4) * 5 % dim
    return jax.jit(lambda A, y, idx: s_step_solve(
        form, plan, A, y, 1e-3, iters, idx=idx)).lower(A, y, idx).compile(
    ).as_text()


@pytest.mark.parametrize("bind", ["raw", "operand"])
def test_dual_reads_x_once_per_outer_step_for_metrics(bind):
    X, y = _problem(512, 200)
    A = X if bind == "raw" else ColMajorOperand.of(X)
    numel = (X if bind == "raw" else A.array).size
    reads = operand_reads(_compiled("dual", A, y, 16), numel, "metrics")
    # One contraction in the outer scan's body; none inside the inner scan
    # (depth 2), none outside the loop.
    assert dict(reads) == {next(iter(reads)): 1}, reads
    assert next(iter(reads))[0] == 1, reads


def test_primal_metrics_read_no_operand():
    X, y = _problem(200, 512)
    op = RowMajorOperand.of(X)
    text = _compiled("primal", op, y, 18)
    assert not operand_reads(text, op.array.size, "metrics")
    # The same reader finds the primal's per-block applies under update.
    assert operand_reads(text, op.array.size, "update")
