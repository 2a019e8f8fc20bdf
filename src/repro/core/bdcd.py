"""Dual block coordinate descent (Algorithm 3) and CA-BDCD (Algorithm 4).

Solves the dual problem

    min_alpha  lam/2 ||X alpha/(lam n)||^2 + 1/(2n) ||alpha + y||^2

with the primal iterate maintained through w = -X alpha / (lam n).  With
b' = 1 this is SDCA with the least-squares loss (paper section 3.2).

Since PR 3 these are thin wrappers over the shared s-step engine: the dual is
a :class:`~repro.core.engine.Formulation` (``DualRidge``) plugged into the
same scan that runs the primal -- same driver, same ragged-tail handling,
same distributed backend.

CA identity: the inner loop is block forward substitution against

    A = Y^T Y / (lam n^2) + O / n,   Y = X[:, flat_idx],  O = overlap(flat_idx)

with base_j = (1/n) (Y_j^T w_sk - alpha_sk[idx_j] - y[idx_j]); diagonal blocks
of A are the Theta_{sk+j} of Eq. (18).

Data flow (panel-free): the dual samples *columns* of X, and the
formulation binds a column-major PacketOperand; which one depends on where
it is built.  Built once (``bind``: a raw X, or a prebuilt
:class:`~repro.kernels.gram.ColMajorOperand`), the operand holds X^T as
row slabs, transposed once, block by block, when it is built; the sampled
Gram ``Y^T Y`` for ``Y = X[:, flat]`` and the deferred primal updates
(Eq. 15/19, ``w -= Y das / (lam n)``) then run the row-DMA kernels
(``kernels/gram/sampled_kernel.py``), which read each sampled column's
bytes once.  Built inside the sharded or pipelined body on every call
(``bind_shard``), the shard keeps its ORIGINAL (dl, n) layout -- no
transpose in the solve body, the ``operand_transpose_free`` contract -- and
its columns come from the lane-aligned column-tile kernels
(``kernels/gram/sampled_colmajor.py``), whose slab fetches over-read by the
128-lane width (``cost_model.packet_hbm_bytes(layout="cols")``).

Either way the operand is the one resident copy of the dataset: an earlier
design pre-transposed each shard (``Xl.T``) inside the solve, a second copy
for its whole length.
"""
from __future__ import annotations

import jax

from .engine import (DualRidge, SolveResult, SolverPlan, register_solver,
                     s_step_solve)

DUAL = DualRidge()


def bdcd(X: jax.Array, y: jax.Array, lam: float, b: int, iters: int,
         key: jax.Array, *, alpha0: jax.Array | None = None,
         idx: jax.Array | None = None, w_ref: jax.Array | None = None,
         impl: str | None = None,
         tiles: tuple[int, int] | None = None) -> SolveResult:
    """Classical BDCD, Algorithm 3: the s-step engine at s=1.  ``b`` is the
    paper's b'.  ``impl`` selects the Gram-packet backend
    (``repro.core.gram_packet``)."""
    plan = SolverPlan(b=b, s=1, impl=impl, tiles=tiles)
    return s_step_solve(DUAL, plan, X, y, lam, iters, key, x0=alpha0, idx=idx,
                        w_ref=w_ref)


def ca_bdcd(X: jax.Array, y: jax.Array, lam: float, b: int, s: int, iters: int,
            key: jax.Array, *, alpha0: jax.Array | None = None,
            idx: jax.Array | None = None, w_ref: jax.Array | None = None,
            track_cond: bool = False, impl: str | None = None,
            tiles: tuple[int, int] | None = None, guard: bool = False,
            fault=None, step0: int = 0) -> SolveResult:
    """CA-BDCD, Algorithm 4: the s-step engine at s>1.  Same index stream as
    :func:`bdcd` => identical iterates in exact arithmetic; one sb' x sb'
    Gram-packet all-reduce per outer iteration in the distributed version
    (backend per ``impl``).  ``iters`` need not be a multiple of ``s``.
    ``guard``/``fault``/``step0`` arm the health guard, the test-only fault
    hook, and the segmented-solve step offset (DESIGN.md section 7)."""
    plan = SolverPlan(b=b, s=s, impl=impl, tiles=tiles, track_cond=track_cond,
                      guard=guard, fault=fault)
    return s_step_solve(DUAL, plan, X, y, lam, iters, key, x0=alpha0, idx=idx,
                        w_ref=w_ref, step0=step0)


# ca_bdcd at s=1 is classical bdcd, so it is the canonical registry entry.
register_solver("dual", "local", ca_bdcd)
