"""The PacketOperand layer (PR 5): layout invariance of the dual engine,
raw-array back-compat, and the MaterializedOperand path.

The tentpole claims pinned here:

* the dual engine's iterates are IDENTICAL between the legacy pre-transposed
  operand (PRs 2-4: ``RowMajorOperand(X.T)``, reconstructed outside the
  engine) and the shipped ``DualRidge``'s operands -- the build-once row
  slabs of X^T, and, in the sharded solve, the column-gather operand over
  the original (d, n) shard -- s=1, s>1, ragged tail, sharded, on ref and
  pallas_interpret, with duplicate and tail-padded column indices;
* both column operands (row slabs of X^T, column gather of X) give the
  transposed row operand's packets, applies and matvecs;
* a pre-materialized kernel-matrix operand (the kernel-BDCD prerequisite,
  arXiv:2406.18001) registers through the operand layer and drives a full
  engine solve with ZERO engine edits -- the formulation below lives in this
  test file and touches only public hooks.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (SolverPlan, bdcd, make_solver_mesh, s_step_solve,
                        s_step_solve_sharded, sample_blocks)
from repro.core.engine import DualRidge
from repro.core.subproblem import block_forward_substitution
from repro.data import SyntheticSpec, make_regression
from repro.kernels.gram import (ColMajorOperand, MaterializedOperand,
                                PacketOperand, RowMajorOperand, as_operand,
                                gram_packet_sampled, gram_packet_sampled_ref,
                                panel_apply, panel_matvec, tuning)
from repro.kernels.gram.operands import _ColGatherOperand

from _legacy_dual import LegacyPreTransposeDual
from _x64 import x64_mode  # noqa: F401  (autouse fixture)

LAM = 1e-3
ITERS = 12
# d is a lane multiple so both layouts pad the contraction identically: with
# pinned equal tiles the kernels then run the same dot_generals in the same
# order and the invariance below is exact, not approximate.
D, N = 128, 96


@pytest.fixture(scope="module")
def problem():
    jax.config.update("jax_enable_x64", True)  # before data gen
    X, y, _ = make_regression(jax.random.key(0),
                              SyntheticSpec("t", d=D, n=N, cond=1e4))
    return X, y


def _dup_idx(key, n_total, b, iters):
    """Index stream whose second inner block repeats the first: every CA
    outer block's flat carries exact duplicate column indices."""
    idx = sample_blocks(key, n_total, b, iters)
    return idx.at[1::2].set(idx[0::2])


def _assert_layout_invariant(impl, a, b):
    """pallas_interpret: BIT-FOR-BIT -- with equal pinned tiles both layouts
    gather value-identical panels (the col kernel's one-hot lane select adds
    only exact +0 terms) and then run the same dot_generals in the same
    order, so every iterate is exactly equal.  ref: exact up to XLA fusion --
    the jnp path is reassociation-unstable by construction (fusing the
    residual matvec with the Gram changes its accumulation order even for
    the SAME expression, measurably: the legacy packet fused differs from
    the legacy packet standalone in the last ulp), so the ref assertion is
    a tight f64 allclose instead."""
    a, b = np.asarray(a), np.asarray(b)
    if impl == "pallas_interpret":
        np.testing.assert_array_equal(a, b)
    else:
        np.testing.assert_allclose(a, b, rtol=1e-11, atol=1e-13)


@pytest.mark.parametrize("impl", ["ref", "pallas_interpret"])
@pytest.mark.parametrize("s", [1, 3, 5], ids=["s1", "s3", "ragged-s5"])
def test_dual_layout_invariance(problem, impl, s):
    """Legacy pre-transposed vs the bound operand (the row slabs of X^T,
    built block by block from X): identical dual iterates (bit-for-bit on
    the kernel path, see _assert_layout_invariant).
    s=3 pads the sb=12 tail of every flat to the 16-row tile (tail-padded
    column indices); s=5 with iters=12 adds the ragged final outer
    iteration; the index stream carries duplicates throughout."""
    X, y = problem
    idx = _dup_idx(jax.random.key(1), N, 4, ITERS)
    # Equal pinned tiles => identical grids and accumulation order in both
    # layouts (d=128 pads the same under the lane and sublane granules).
    tiles = (128, 128) if impl == "pallas_interpret" else None
    plan = SolverPlan(b=4, s=s, impl=impl, tiles=tiles)
    r_leg = s_step_solve(LegacyPreTransposeDual(), plan, X, y, LAM, ITERS,
                         None, idx=idx)
    r_col = s_step_solve(DualRidge(), plan, X, y, LAM, ITERS, None, idx=idx)
    _assert_layout_invariant(impl, r_col.w, r_leg.w)
    _assert_layout_invariant(impl, r_col.alpha, r_leg.alpha)
    _assert_layout_invariant(impl, r_col.history["objective"],
                             r_leg.history["objective"])


@pytest.mark.parametrize("impl", ["ref", "pallas_interpret"])
def test_dual_layout_invariance_sharded(problem, impl):
    """Same invariance through the shard_map backend, whose shard-local
    operand is the column gather over the original layout (single-device
    mesh in this process; the 8-device world re-checks it in dist_checks)."""
    X, y = problem
    mesh = make_solver_mesh(1)
    idx = _dup_idx(jax.random.key(2), N, 4, ITERS)
    tiles = (128, 128) if impl == "pallas_interpret" else None
    plan = SolverPlan(b=4, s=4, impl=impl, tiles=tiles)
    w_leg, al_leg = s_step_solve_sharded(LegacyPreTransposeDual(), plan, mesh,
                                         X, y, LAM, ITERS, None, idx=idx)
    w_col, al_col = s_step_solve_sharded(DualRidge(), plan, mesh, X, y, LAM,
                                         ITERS, None, idx=idx)
    _assert_layout_invariant(impl, w_col, w_leg)
    _assert_layout_invariant(impl, al_col, al_leg)


def test_dual_solver_binds_original_layout(problem):
    """The dual formulation's two operands.  ``bind`` (built once) holds the
    sample-major row slabs of X^T, (n, d_pad/128, 128): padded X^T, the one
    transposed copy; a prebuilt operand binds as is, with no second copy.
    ``bind_shard`` (built inside the sharded body on every call) keeps the
    ORIGINAL (d, n) orientation, zero-padded, for the column gather."""
    X, y = problem
    bound = DualRidge().bind(X, y, LAM)
    assert isinstance(bound.operand, ColMajorOperand)
    assert bound.operand.shape == (D, N)
    assert bound.operand.samples == N and bound.operand.contraction == D
    arr = bound.operand.array
    d_pad = tuning.padded_contraction(D, X.dtype, "rows")
    assert arr.shape == (N, d_pad // 128, 128)
    panel = arr.reshape(N, d_pad)
    np.testing.assert_array_equal(panel[:, :D], X.T)
    assert not np.asarray(panel[:, D:]).any()
    bound_sh = DualRidge().bind_shard(X, y, LAM, d=D, n=N)
    assert isinstance(bound_sh.operand, _ColGatherOperand)
    gat = bound_sh.operand.array
    assert gat.shape[0] >= D and gat.shape[1] >= N
    np.testing.assert_array_equal(gat[:D, :N], X)
    assert not np.asarray(gat[D:]).any() and not np.asarray(gat[:, N:]).any()
    op = ColMajorOperand(X)
    bound_op = DualRidge().bind(op, y, LAM)
    assert bound_op.operand is op and bound_op.X is None


@pytest.mark.parametrize("shape", [(23, 70), (1030, 300), (2049, 129),
                                   (1100, 128)],
                         ids=["n<block", "n>block", "n=block+1", "n=block"])
def test_col_slabs_equal_transposed_row_slabs(shape):
    """The transposing slab build, block by block, is bitwise the row slabs
    of X^T: d off the 1,024 contraction unit, n off the 128-column block
    (smaller, larger, one past) and on it."""
    d, n = shape
    X = jax.random.normal(jax.random.key(10), (d, n), jnp.float64)
    col = ColMajorOperand(X)
    row = RowMajorOperand(X.T)
    assert col.array.shape == row.array.shape
    np.testing.assert_array_equal(col.array, row.array)
    assert (col.samples, col.contraction) == (n, d) and col.shape == (d, n)


# --------------------------------------------------------------------------
# Dispatch-level: raw-array back-compat and the column operand's semantics
# --------------------------------------------------------------------------

def test_as_operand_raw_array_means_rows(problem):
    X, _ = problem
    op = as_operand(X)
    assert isinstance(op, RowMajorOperand) and op.shape == X.shape
    np.testing.assert_array_equal(
        op.array.reshape(D, -1)[:, :N], X)       # the (d, n/128, 128) slabs
    assert as_operand(op) is op and RowMajorOperand.of(op) is op
    col = ColMajorOperand(X)
    assert as_operand(col) is col
    assert isinstance(col, PacketOperand)          # runtime protocol check


@pytest.mark.parametrize("cols", [ColMajorOperand, _ColGatherOperand],
                         ids=["slabs", "gather"])
def test_colmajor_matches_transposed_rowmajor(problem, cols):
    """Both column operands of X == RowMajorOperand(X.T) on every entry
    point: the packet, the deferred apply, and the sample-side matvec."""
    X, _ = problem
    flat = jnp.asarray([5, 5, 0, 90, 7, 7, 7, 1, 0, 19, 3, 2, 11], jnp.int32)
    u = jax.random.normal(jax.random.key(3), (D,), jnp.float64)
    v = jax.random.normal(jax.random.key(4), (13,), jnp.float64)
    for impl in ("ref", "pallas_interpret"):
        G0, r0 = gram_packet_sampled(RowMajorOperand(X.T), flat, u,
                                     scale=1.0 / N, reg=0.5, scale_r=2.0,
                                     impl=impl)
        G1, r1 = gram_packet_sampled(cols(X), flat, u,
                                     scale=1.0 / N, reg=0.5, scale_r=2.0,
                                     impl=impl)
        np.testing.assert_allclose(G1, G0, rtol=0, atol=1e-10)
        np.testing.assert_allclose(r1, r0, rtol=0, atol=1e-10)
        a0 = panel_apply(RowMajorOperand(X.T), flat, v, scale=0.7, impl=impl)
        a1 = panel_apply(cols(X), flat, v, scale=0.7, impl=impl)
        np.testing.assert_allclose(a1, a0, rtol=0, atol=1e-10)
    t = jax.random.normal(jax.random.key(5), (D,), jnp.float64)
    m0 = panel_matvec(RowMajorOperand(X.T), flat, t, scale=1.3, impl="ref")
    m1 = panel_matvec(cols(X), flat, t, scale=1.3, impl="ref")
    np.testing.assert_allclose(m1, m0, rtol=0, atol=1e-10)


@pytest.mark.parametrize("cols", [ColMajorOperand, _ColGatherOperand],
                         ids=["slabs", "gather"])
def test_colmajor_ragged_nonaligned(problem, cols):
    """Ragged everything: d not a sublane multiple, n not a lane multiple,
    duplicate and repeated-0 indices -- pad/unpad is exact in f64, for both
    column operands."""
    d, n = 23, 70
    X = jax.random.normal(jax.random.key(6), (d, n), jnp.float64)
    u = jax.random.normal(jax.random.key(7), (d,), jnp.float64)
    flat = jnp.asarray([5, 5, 0, 22, 7, 7, 7, 1, 0, 19, 3, 2, 11], jnp.int32)
    G0, r0 = gram_packet_sampled_ref(X.T, flat, u, 1.0 / n, 0.5, 2.0)
    G1, r1 = gram_packet_sampled(cols(X), flat, u, scale=1.0 / n,
                                 reg=0.5, scale_r=2.0,
                                 impl="pallas_interpret")
    assert G1.shape == (13, 13) and r1.shape == (13,)
    np.testing.assert_allclose(G1, G0, rtol=0, atol=1e-10)
    np.testing.assert_allclose(r1, r0, rtol=0, atol=1e-10)


# --------------------------------------------------------------------------
# MaterializedOperand: the kernel-BDCD prerequisite, smoke-level
# --------------------------------------------------------------------------

def test_materialized_operand_dispatch(problem):
    """G is GATHERED (scale * K[flat][:, flat] + reg*I), r/apply/matvec run
    against K's sampled rows -- through the same public entry points."""
    X, _ = problem
    K = X.T @ X
    flat = jnp.asarray([3, 3, 0, 40, 8], jnp.int32)
    u = jax.random.normal(jax.random.key(8), (N,), jnp.float64)
    v = jax.random.normal(jax.random.key(9), (5,), jnp.float64)
    op = MaterializedOperand(K)
    assert op.samples == N and op.contraction == N
    for impl in ("ref", "pallas", "pallas_interpret"):
        G, r = gram_packet_sampled(op, flat, u, scale=2.0, reg=0.25,
                                   impl=impl)
        np.testing.assert_allclose(
            G, 2.0 * K[flat][:, flat] + 0.25 * jnp.eye(5), rtol=0, atol=1e-9)
        np.testing.assert_allclose(r, 2.0 * K[flat, :] @ u, rtol=1e-12,
                                   atol=1e-9)
    np.testing.assert_allclose(panel_apply(op, flat, v, scale=0.5),
                               0.5 * K[flat, :].T @ v, rtol=1e-12, atol=1e-9)
    np.testing.assert_allclose(panel_matvec(op, flat, u, scale=0.5),
                               0.5 * K[flat, :] @ u, rtol=1e-12, atol=1e-9)


class KernelDualRidge:
    """Smoke-level kernel BDCD (arXiv:2406.18001): the dual formulation over
    a pre-materialized kernel matrix K = X^T X.  Defined ENTIRELY here --
    public Formulation hooks + MaterializedOperand -- which is the proof
    that the operand layer admits the kernel-matrix operand with zero
    engine.py edits.  The carry is (z, alpha) with z = -K alpha / (lam n)
    (the kernel-space image of X^T w), so for the linear kernel the iterates
    must match ``bdcd`` exactly in exact arithmetic."""
    name = "kernel-dual-smoke"
    operand_layout = "materialized"

    def sample_dim(self, d, n):
        return n

    def bind(self, K, y, lam, *, x0=None, w_ref=None):
        n = K.shape[0]
        op = MaterializedOperand(K)

        @dataclasses.dataclass(frozen=True)
        class _Bound:
            operand: object
            scale = 1.0 / (lam * n * n)
            scale_r = -1.0 / (lam * n)
            reg = 1.0 / n

            def init_carry(self, axes=None):
                z = jnp.zeros((n,), K.dtype)
                return z, jnp.zeros((n,), K.dtype)

            def packet_vector(self, carry):
                return carry[1]                       # alpha: r = -K_f a/(ln)

            def base(self, u, carry, flat):
                z, alpha = carry
                return (u - alpha[flat] - y[flat]) / n

            def inner_sweep(self, A, base, s_k, b, flat, carry, overlap=None):
                return block_forward_substitution(A, base, s_k, b)

            def update(self, carry, idx, dx, pp):
                z, alpha = carry
                alpha = alpha.at[idx].add(dx)
                z = z - panel_apply(self.operand, idx, dx,
                                    plan=pp) / (lam * n)
                return z, alpha

            def metrics(self, carry):
                z, alpha = carry
                r = z - y
                w_sq = -(alpha @ z) / (lam * n)       # ||w||^2 via the kernel
                return {"objective": 0.5 / n * (r @ r) + 0.5 * lam * w_sq}

        return _Bound(operand=op)


@pytest.mark.parametrize("s", [1, 4])
def test_materialized_engine_smoke(problem, s):
    """A full engine solve on the kernel-matrix operand: for the linear
    kernel K = X^T X, kernel BDCD == BDCD (alpha and the dual residual
    z = X^T w), s=1 and s>1, through the unmodified engine."""
    X, y = problem
    K = X.T @ X
    idx = sample_blocks(jax.random.key(10), N, 4, ITERS)
    plan = SolverPlan(b=4, s=s, impl="ref")
    res = s_step_solve(KernelDualRidge(), plan, K, y, LAM, ITERS, None,
                       idx=idx)
    ref = bdcd(X, y, LAM, 4, ITERS, None, idx=idx, impl="ref")
    z, alpha = res.w, res.alpha                      # carry = (z, alpha)
    np.testing.assert_allclose(alpha, ref.alpha, rtol=1e-9, atol=1e-11)
    np.testing.assert_allclose(z, X.T @ ref.w, rtol=1e-8, atol=1e-10)
    np.testing.assert_allclose(res.history["objective"],
                               ref.history["objective"], rtol=1e-8, atol=0)
