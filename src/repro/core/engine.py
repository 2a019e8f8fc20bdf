"""The one s-step engine behind every (CA-)BCD / (CA-)BDCD variant.

The paper's communication-avoiding transform is a single algorithmic idea
(DESIGN.md section 5): sample ``s`` coordinate blocks up front, build ONE
``sb x sb`` Gram packet at the single communication point, then run ``s``
communication-free inner solves by block forward substitution.  Everything
that distinguishes the primal from the dual solver -- which operand's rows
are sampled, the packet's scale/regularizer, the subproblem right-hand side,
which iterate the deferred update touches -- is data, not control flow.  This
module therefore factors the repo's former six hand-rolled solver loops
(``bcd``/``ca_bcd``, ``bdcd``/``ca_bdcd``, and the two shard_map variants)
into

* a :class:`Formulation` (primal / dual): the handful of problem-specific
  hooks above, bound to concrete operands by ``bind`` / ``bind_shard`` --
  the operand is a :class:`~repro.kernels.gram.PacketOperand` (array +
  layout + gather strategy, DESIGN.md section 5.2), so "which axis is
  sampled and how" is the operand's business, not the engine's: the primal
  binds row-major X, the dual binds COLUMN-major X (built once as the row
  slabs of X^T; a shard inside the sharded body gathers the columns of its
  original (dl, n) layout, with no transpose), and a pre-materialized
  kernel matrix binds through the same dispatch with zero engine edits;
* a :class:`SolverPlan`: the execution knobs (b, s, backend ``impl``, kernel
  ``tiles``, ``fuse_packet``, ``unroll``, ``track_cond``) -- ``s=1`` *is* the
  classical variant, not a separate loop;
* ONE driver, :func:`s_step_solve`, whose outer ``lax.scan`` body
  (:func:`_outer_step`) is the only s-step hot loop in the repo.  The
  distributed path (:func:`s_step_solve_sharded`) wraps the *same* driver in
  ``shard_map`` and flips exactly one switch: the packet regularizer moves
  out of the kernel and an all-reduce (:func:`_packet_reduce`) is inserted at
  the one communication point.

``iters`` need not be a multiple of ``s``: the driver runs ``iters // s`` full outer
iterations through the scan and, when ``iters % s != 0``, one ragged final
outer iteration through the same body with ``s_k = iters % s`` -- the CA
identity holds for any grouping of the index stream, so the iterates still
match the classical schedule bit-for-bit in exact arithmetic.

New formulations plug in by implementing the Formulation hooks and
registering under a name -- no new loop, no new shard_map.  The proximal
elastic-net methods of arXiv:1712.06047 are ``repro.core.proximal`` (the
first formulation added *through* the registry; its nonsmooth update rides
the ``inner_sweep`` hook); the kernel BDCD of arXiv:2406.18001 is the next
candidate.  The registry (:func:`register_solver` / :func:`get_solver`,
keyed on ``(formulation, backend)``) is how launch scripts, benchmarks, and
examples select solvers.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, NamedTuple, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.kernels.gram import (ColMajorOperand, PacketOperand, PacketPlan,
                                RowMajorOperand, gram_packet_sampled,
                                panel_apply, panel_matvec)
from repro.kernels.gram.operands import _ColGatherOperand
from repro.kernels.gram.ops import _check_positive_int, _pad_axis

from .hlo_analysis import ENGINE_PHASES, PHASE_PREFIX
from .sampling import overlap_matrix, sample_blocks
from .subproblem import block_forward_substitution, choose_jitter


class SolveResult(NamedTuple):
    w: jax.Array          # (d,) primal iterate
    alpha: jax.Array      # (n,) auxiliary iterate (X^T w primal; dual vector)
    history: dict         # metric name -> (iters,) array (per inner iteration)
    metrics: dict = {}    # end-of-solve scalars (guard/recovery telemetry)


@dataclasses.dataclass(frozen=True)
class SolverContracts:
    """The communication/memory guarantees a formulation DECLARES -- and the
    static contract engine (``repro.analysis``) verifies against every
    registered lowering.

    The paper's headline result is a contract, not a number: CA-BCD/CA-BDCD
    synchronize exactly once per outer iteration (arXiv:1612.04003), the
    proximal variant inherits the same structure (arXiv:1712.06047), and the
    PR-2/PR-5 guarantees (panel never materializes; the dual binds the
    original layout with no transpose) are structural properties of the
    compiled HLO.  Each formulation states its invariants here instead of
    inheriting silent assumptions; ``python -m repro.analysis sweep`` lowers
    every ``(formulation, backend)`` registry entry and fails when a declared
    contract breaks.  A formulation without a ``contracts()`` hook FAILS the
    sweep -- declaring is mandatory, not optional.

    * ``sync_per_outer``: collectives per outer iteration on the sharded
      backend (1 for every paper formulation -- the single packet
      all-reduce).  A future pipelined-collective formulation would declare
      its own count here rather than silently widening the budget.
    * ``collective_kinds``: the only collective opcodes allowed to appear in
      the sharded lowering at all.
    * ``local_collective_free``: the local backend must lower with ZERO
      cross-device collectives.
    * ``operand_transpose_free``: no HLO transpose of the bound operand's
      (local) array anywhere in the sharded solve body -- the PR-5 "no dual
      pre-transpose" guarantee, checked shape-against-shape.
    * ``panel_free_impls``: kernel backends whose lowering must never
      materialize the sampled ``(sb, contraction)`` panel outside a Pallas
      custom-call (the ``impl="ref"`` path gathers the panel by design, so
      it is not listed).
    * ``f64_packet``: under the x64 test path every collective must move f64
      words (the packet may not silently downcast accumulation).
    * ``health_in_packet``: the formulation supports ``SolverPlan.guard``
      with the per-outer-step health word riding the ONE packet all-reduce
      (DESIGN.md section 7) -- the analysis engine additionally lowers the
      guard-enabled solver and asserts the collective count is UNCHANGED
      (exactly ``sync_per_outer * H``): the zero-extra-collectives guarantee.
    * ``lowering_kwargs``: extra solver kwargs ((key, value) pairs) the
      analysis engine passes when lowering this formulation abstractly, so
      formulation-specific code paths (e.g. the proximal soft-threshold at
      ``lam1 > 0``) are the ones verified.
    * ``tenant_batched``: the formulation supports the batched multi-tenant
      engine (:func:`s_step_solve_batched`) -- its per-tenant coefficients
      flow through ``bind``/``dataclasses.replace`` under ``vmap`` and its
      sharded batched lowering keeps ``sync_per_outer`` collectives per
      outer step INDEPENDENT of the tenant count, with the Gram part of the
      packet payload not scaled by T (DESIGN.md section 8; the analysis
      sweep lowers batched cases at T in {1, 8, 64} and checks both).
    * ``pipelined_collective_kinds`` / ``pipelined_hops``: the wire schedule
      of the PIPELINED backend (``SolverPlan.wire == "ring"``, DESIGN.md
      section 9).  The kinds tuple is the only collective opcodes allowed in
      the pipelined lowering; ``pipelined_hops`` is the per-sync op count as
      an affine law ``(a, c)`` meaning ``sum_i (a * P_i + c)`` over the mesh
      axis sizes -- the default ``(2, -2)`` is the two-phase ring's
      ``2 (P_i - 1)`` collective-permute hops per axis.  The analysis sweep
      computes the expected count from the mesh it lowers on
      (:func:`ring_hops`), so a backend with a different decomposition
      declares its law here instead of hand-editing count asserts.
    """
    sync_per_outer: int = 1
    collective_kinds: tuple = ("all-reduce",)
    local_collective_free: bool = True
    operand_transpose_free: bool = True
    panel_free_impls: tuple = ("pallas", "pallas_interpret")
    f64_packet: bool = True
    health_in_packet: bool = False
    lowering_kwargs: tuple = ()
    tenant_batched: bool = False
    pipelined_collective_kinds: tuple = ("collective-permute",)
    pipelined_hops: tuple = (2, -2)


@dataclasses.dataclass(frozen=True)
class SolverPlan:
    """Everything the engine needs to know besides the problem data.

    ``b`` is the paper's block size (b' for the dual), ``s`` the loop-blocking
    parameter (s=1 recovers the classical algorithm).  ``impl``/``tiles``
    select the Gram-packet kernel backend and its (bm, bk) -- collapsed into
    one :class:`~repro.kernels.gram.PacketPlan` handed to every kernel call.
    ``fuse_packet`` picks the wire layout of the distributed reduction (see
    :func:`_packet_reduce`); ``unroll`` is forwarded to the outer scan;
    ``track_cond`` records cond(Gram) per outer iteration in the history.

    ``wire`` picks the reduction SCHEDULE of the distributed backends:
    ``"psum"`` (default) is the monolithic packet all-reduce; ``"ring"`` is
    the pipelined backend's collective-permute decomposition -- a two-phase
    ring of ``ppermute`` hops per mesh axis with the next outer step's Gram
    contraction software-pipelined between the phases (DESIGN.md section 9).
    The iterates agree to f64 ~1e-12 (the ring's summation order differs
    from psum's tree, so bit-for-bit is not guaranteed across wires).

    ``guard`` enables the in-scan health guards (DESIGN.md section 7): a
    per-outer-step health word rides the ONE packet reduction (zero extra
    collectives) and a tripped guard degrades the step -- adaptive diagonal
    jitter or a skipped update -- instead of corrupting ``s`` deferred
    iterations.  ``guard_boost`` is the divergence/magnitude envelope margin
    (trip when the tracked quantity exceeds ``boost x`` its running floor);
    ``guard_cond_max`` caps the Gram-diagonal ratio condition proxy (``None``
    picks ``0.1 / eps(dtype)``).  ``fault`` attaches a test-only
    :class:`repro.faults.FaultPlan` (duck-typed: anything with
    ``apply_packet`` / ``apply_health``) injected inside the hot loop.

    ``tenants`` pins the tenant-axis width of a batched solve (DESIGN.md
    section 8): ``None`` means "whatever the :class:`TenantBatch` carries";
    a pinned value makes the plan itself the compile-cache key for a
    (bucket, formulation) pair -- the batched entry points reject a batch
    whose width disagrees instead of silently recompiling.
    """
    b: int
    s: int = 1
    impl: str | None = None
    tiles: tuple[int, int] | None = None
    fuse_packet: bool = True
    unroll: int = 1
    track_cond: bool = False
    guard: bool = False
    guard_boost: float = 1e4
    guard_cond_max: float | None = None
    fault: object | None = None
    tenants: int | None = None
    wire: str = "psum"

    def __post_init__(self):
        # Fail fast at plan construction: a typo'd impl or a zero tile would
        # otherwise only surface at the first kernel call inside the jitted
        # scan (or, worse, silently fall through to the autotuned tiles).
        for name in ("b", "s", "unroll"):
            _check_positive_int(f"SolverPlan.{name}", getattr(self, name))
        if self.tiles is not None and len(self.tiles) != 2:
            raise ValueError(
                f"SolverPlan.tiles={self.tiles!r} must be a (bm, bk) pair")
        if not isinstance(self.guard, bool):
            raise ValueError(f"SolverPlan.guard={self.guard!r} must be a bool")
        if not self.guard_boost > 1:
            raise ValueError(
                f"SolverPlan.guard_boost={self.guard_boost!r} must be > 1")
        if self.guard_cond_max is not None and not self.guard_cond_max > 1:
            raise ValueError(
                f"SolverPlan.guard_cond_max={self.guard_cond_max!r} "
                "must be > 1 (or None for the dtype default)")
        if self.fault is not None and not (
                hasattr(self.fault, "apply_packet")
                and hasattr(self.fault, "apply_health")):
            raise ValueError(
                f"SolverPlan.fault={self.fault!r} must provide "
                "apply_packet/apply_health (see repro.faults.FaultPlan)")
        if self.tenants is not None:
            _check_positive_int("SolverPlan.tenants", self.tenants)
        if self.wire not in ("psum", "ring"):
            raise ValueError(
                f"SolverPlan.wire={self.wire!r} must be 'psum' or 'ring'")
        self.packet  # PacketPlan.make validates impl and the tile values

    @property
    def packet(self) -> PacketPlan:
        return PacketPlan.make(impl=self.impl, tiles=self.tiles)


@runtime_checkable
class BoundFormulation(Protocol):
    """A formulation bound to concrete operands (global or one shard's).

    ``operand`` is a :class:`~repro.kernels.gram.PacketOperand` -- the array
    plus its layout and gather strategy (DESIGN.md section 5.2).  The engine
    samples the operand's index space; the packet it builds is
    ``G = scale * Y Y^T + reg * I`` and ``r = scale_r * Y u`` for the
    operand's sampled panel ``Y(flat)`` (rows of the array for the primal's
    row-major operand, columns of the ORIGINAL layout for the dual's
    column-major operand, gathered pre-formed products for a materialized
    kernel matrix) and ``u = packet_vector(carry)``.  ``reg`` is also the
    coefficient of the duplicate-index overlap term, which is why a single
    scalar serves both the fused local diagonal and the post-reduce
    correction.

    ``inner_sweep`` owns the subproblem solve: given the replicated
    ``sb x sb`` system ``A`` and right-hand side ``base`` it returns the
    ``sb`` applied block updates.  The ridge formulations delegate to
    :func:`~repro.core.subproblem.block_forward_substitution`; nonsmooth
    formulations (the proximal elastic net) run the prox-aware variant --
    the hook exists precisely so a formulation can reshape each block's
    applied step without touching the engine's one hot-loop body.
    """
    operand: PacketOperand

    @property
    def scale(self) -> float: ...
    @property
    def scale_r(self) -> float | None: ...
    @property
    def reg(self) -> float: ...
    def init_carry(self, axes: tuple | None = None) -> tuple: ...
    def packet_vector(self, carry) -> jax.Array: ...
    def base(self, r: jax.Array, carry, flat: jax.Array) -> jax.Array: ...
    def inner_sweep(self, A: jax.Array, base: jax.Array, s_k: int, b: int,
                    flat: jax.Array, carry,
                    overlap: jax.Array | None) -> jax.Array: ...
    def update(self, carry, idx: jax.Array, dx: jax.Array,
               pp: PacketPlan) -> tuple: ...
    def metrics(self, carry) -> dict: ...


class Formulation(Protocol):
    """A problem formulation: how to bind data to a :class:`BoundFormulation`
    and how its operands shard (DESIGN.md section 5.3).  ``operand_layout``
    names the PacketOperand kind ``bind``/``bind_shard`` produce (DESIGN.md
    section 5.2) -- introspection only (dry-runs, benchmarks); the engine
    itself dispatches through the operand object."""
    name: str
    operand_layout: str

    def contracts(self) -> SolverContracts: ...
    def sample_dim(self, d: int, n: int) -> int: ...
    def bind(self, X, y, lam, *, x0=None, w_ref=None) -> BoundFormulation: ...
    def pad_shards(self, X, y, n_shards: int) -> tuple: ...
    def bind_shard(self, Xl, yl, lam, *, d: int, n: int,
                   x0=None) -> BoundFormulation: ...
    def dist_in_specs(self, axis) -> tuple: ...
    def dist_out_specs(self, axis) -> tuple: ...
    def dist_finalize(self, w, alpha, d: int, n: int) -> tuple: ...


# --------------------------------------------------------------------------
# Shared metric helpers
# --------------------------------------------------------------------------

def _objective_from_alpha(alpha, w, y, lam):
    # alpha == X^T w is maintained by the residual-form recurrence, so the
    # objective costs O(n + d) per iteration instead of O(dn).
    n = alpha.shape[0]
    r = alpha - y
    return 0.5 / n * (r @ r) + 0.5 * lam * (w @ w)


def _sol_err(w, w_ref):
    return jnp.linalg.norm(w - w_ref) / jnp.linalg.norm(w_ref)


def _fit_residual(alpha, y):
    # ||alpha - y|| / (1 + ||y||): the O(n) data-fit proxy the batched
    # engine's early-retirement mask thresholds (DESIGN.md section 8).  A
    # relative statistic, monotone along the solve, cheap enough to ride
    # every outer step; NOT a stationarity certificate (the ridge optimum
    # has a nonzero fit residual), so retirement tolerances are calibrated
    # per workload, not read as gradient norms.
    return jnp.linalg.norm(alpha - y) / (1.0 + jnp.linalg.norm(y))


# --------------------------------------------------------------------------
# Primal formulation: min_w lam/2 ||w||^2 + 1/(2n) ||X^T w - y||^2
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class _BoundPrimal:
    """Algorithm 1/2 hooks; ``operand`` is the row-major X (d, n) or a column
    shard of it.

    Packet: Gamma = Y Y^T / n + lam I with Y = X[flat, :] and the residual
    contribution Y (y - alpha) / n of the Eq. (7)/(8) rhs; base subtracts the
    lam w term; the inner update is w[idx] += dw, alpha += Y_j^T dw (Eqs. 5,
    9-10).  All expressions are layout-neutral: on a column shard (y and
    alpha local, w replicated) they compute exactly the local contribution.
    """
    operand: PacketOperand
    y: jax.Array            # aligned with operand's columns
    lam: float
    n: int                  # GLOBAL data-point count (scales use it)
    d: int
    w0: jax.Array | None = None
    w_ref: jax.Array | None = None

    @property
    def scale(self):
        return 1.0 / self.n

    @property
    def scale_r(self):
        return None         # defaults to scale

    @property
    def reg(self):
        return self.lam

    def init_carry(self, axes=None):
        dtype = self.operand.dtype
        w = jnp.zeros((self.d,), dtype) if self.w0 is None else self.w0
        if axes is not None:
            # alpha is device-varying (each shard owns a slice of R^n); w is
            # replicated.  A warm-started w derives its local alpha slice as
            # ``Xl^T w`` -- a dense product, no gather -- which is what lets
            # the supervised restart path re-enter the sharded solve from a
            # checkpointed iterate (DESIGN.md section 7).
            if self.w0 is not None:
                return w, self.operand.full_apply(w)
            return w, jax.lax.pcast(jnp.zeros(self.y.shape, dtype), axes,
                                    to="varying")
        alpha = (self.operand.full_apply(w) if self.w0 is not None
                 else jnp.zeros((self.n,), dtype))
        return w, alpha

    def packet_vector(self, carry):
        return self.y - carry[1]

    def base(self, r, carry, flat):
        # Eq. (7)/(8) rhs.  The lam*w mul/sub seam may fma-contract, which
        # is fine BECAUSE every context that evaluates it is a compiled body
        # running this same graph (the drivers share _assemble_subproblem
        # and the ragged tail is scanned) -- see _assemble_subproblem.
        return r - self.lam * carry[0][flat]

    def inner_sweep(self, A, base, s_k, b, flat, carry, overlap=None):
        return block_forward_substitution(A, base, s_k, b)

    def update(self, carry, idx, dx, pp):
        w, alpha = carry
        w = w.at[idx].add(dx)                              # Eq. (9)
        alpha = alpha + panel_apply(self.operand, idx, dx, plan=pp)  # Eq. (5)/(10)
        return w, alpha

    def metrics(self, carry):
        w, alpha = carry
        m = {"objective": _objective_from_alpha(alpha, w, self.y, self.lam),
             "residual": _fit_residual(alpha, self.y)}
        if self.w_ref is not None:
            m["sol_err"] = _sol_err(w, self.w_ref)
        return m


class PrimalRidge:
    """(CA-)BCD: samples features (rows of X); 1D-block-column layout."""
    name = "primal"
    operand_layout = "rows"

    def contracts(self):
        # Theorem 1/6 structure: ONE fused packet all-reduce per outer
        # iteration, nothing else on the wire; row-major operand, no
        # transpose, panel-free kernel path.  The health word rides that
        # same all-reduce (guard mode adds zero collectives).  All the
        # scales are tenant-independent, so the batched engine shares the
        # fully-scaled Gram across tenants.
        return SolverContracts(health_in_packet=True, tenant_batched=True)

    def sample_dim(self, d, n):
        return d

    def bind(self, X, y, lam, *, x0=None, w_ref=None):
        d, n = X.shape
        return _BoundPrimal(operand=RowMajorOperand.of(X), y=y, lam=lam,
                            n=n, d=d, w0=x0, w_ref=w_ref)

    def pad_shards(self, X, y, n_shards):
        return _pad_to(X, n_shards, 1), _pad_to(y, n_shards, 0)

    def bind_shard(self, Xl, yl, lam, *, d, n, x0=None):
        return _BoundPrimal(operand=RowMajorOperand(Xl), y=yl, lam=lam, n=n,
                            d=d, w0=x0)

    def dist_in_specs(self, axis):
        return P(None, axis), P(axis), P(None)

    def dist_out_specs(self, axis):
        return P(None), P(axis)

    def dist_finalize(self, w, alpha, d, n):
        return w, alpha[:n]


# --------------------------------------------------------------------------
# Dual formulation: min_alpha lam/2 ||X alpha/(lam n)||^2 + 1/(2n) ||alpha + y||^2
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class _BoundDual:
    """Algorithm 3/4 hooks; ``operand`` is the column-major X (d, n) -- or a
    row shard Xl (dl, n).  The dual samples *columns* of X.  The build-once
    operand (``bind``) holds X^T as row slabs, transposed once when it is
    built, and runs the row-DMA kernels; the shard-local operand
    (``bind_shard``, built inside the sharded body on every call) gathers
    columns of the ORIGINAL (dl, n) layout (``sampled_colmajor.py``), so the
    sharded solve body holds no transpose of the operand.  Either way the
    operand is the one resident copy of the dataset (see
    ``repro.core.bdcd``).

    Packet: Theta = Y^T Y / (lam n^2) + I/n with Y = X[:, flat] plus the RAW
    projection Y^T w (scale_r=1); base assembles Eq. (17)/(18); the inner
    update is alpha[idx] += da, w -= Y_j da / (lam n) (Eqs. 15, 19-20).  On a
    row shard (w local, alpha and y replicated) the same expressions compute
    the local contribution.
    """
    operand: PacketOperand
    y: jax.Array            # (n,), replicated in the distributed layout
    lam: float
    n: int                  # GLOBAL data-point count
    X: jax.Array | None = None      # raw X for init + metrics, when bound
    alpha0: jax.Array | None = None
    w_ref: jax.Array | None = None
    # Pinned derived constants (see DualRidge.tenant_constants): with a
    # python-float lam the properties below compute these in f64 host
    # arithmetic, but a traced per-tenant lam would round every intermediate
    # to f32 -- an ulp off the single solve.  The batched engine pins the
    # host-computed values here instead.
    scale_c: object = None
    lam_n: object = None

    @property
    def scale(self):
        if self.scale_c is not None:
            return self.scale_c
        return 1.0 / (self.lam * self.n * self.n)

    @property
    def _div(self):
        """The Eq. (15)/(19) divisor lam*n, host-exact when pinned.  Always
        returned as an optimization-barriered runtime value: an embedded
        python-float divisor gets constant-folded by XLA into a reciprocal
        multiply (an ulp off a true division), while the batched engine's
        pinned per-tenant divisor is a traced array that divides for real --
        the barrier forces the true division in every context."""
        div = self.lam * self.n if self.lam_n is None else self.lam_n
        if isinstance(div, (int, float)):
            div = jax.lax.optimization_barrier(
                jnp.asarray(div, self.operand.dtype))
        return div

    @property
    def scale_r(self):
        return 1.0

    @property
    def reg(self):
        return 1.0 / self.n

    def init_carry(self, axes=None):
        dtype = self.operand.dtype
        if axes is not None:
            # w is device-varying (each shard owns a slice of R^d); alpha is
            # replicated.  The operand's contraction length IS the local dl.
            # A warm-started alpha derives its local w slice straight from
            # the ORIGINAL (dl, n) layout -- checkpointed restarts re-enter
            # the sharded solve transpose-free (DESIGN.md section 7).
            if self.alpha0 is not None:
                q = jax.lax.optimization_barrier(
                    self.operand.full_apply(self.alpha0))
                return -q / self._div, self.alpha0
            wl = jax.lax.pcast(jnp.zeros((self.operand.contraction,), dtype),
                               axes, to="varying")
            return wl, jnp.zeros((self.n,), dtype)
        alpha = jnp.zeros((self.n,), dtype) if self.alpha0 is None else self.alpha0
        q = jax.lax.optimization_barrier(
            self.operand.full_apply(alpha) if self.X is None
            else self.X @ alpha)
        return -q / self._div, alpha

    def packet_vector(self, carry):
        return carry[0]

    def base(self, u, carry, flat):
        w, alpha = carry
        num = u - alpha[flat] - self.y[flat]
        # Eq. (17)/(18).  Barriered divisor for the same reason as _div:
        # a python-int n constant-folds to a reciprocal multiply inside
        # compiled bodies but divides for real eagerly -- the barrier
        # forces the true division in every context.
        return num / jax.lax.optimization_barrier(
            jnp.asarray(self.n, num.dtype))

    def inner_sweep(self, A, base, s_k, b, flat, carry, overlap=None):
        return block_forward_substitution(A, base, s_k, b)

    def update(self, carry, idx, dx, pp):
        w, alpha = carry
        alpha = alpha.at[idx].add(dx)                      # Eq. (20)
        # Eq. (15)/(19): w -= X[:, idx] @ dx / (lam n) -- the column-major
        # operand's Y^T v, straight from the original layout.  The barriers
        # pin the rounding sequence (gather-apply, then divide, then
        # subtract): XLA otherwise fuses the division into whichever
        # producer the surrounding context offers, and the single-solve scan
        # and the tenant-batched scan offer different ones -- an ulp apart.
        ap = jax.lax.optimization_barrier(
            panel_apply(self.operand, idx, dx, plan=pp))
        w = w - jax.lax.optimization_barrier(ap / self._div)
        return w, alpha

    def metrics(self, carry):
        # Primal objective evaluated at the dual-generated primal iterate w.
        # X^T w is O(dn): a full pass over the operand.  The local solve's
        # history therefore goes through ``metrics_stacked``, one pass per
        # outer step for all of its s_k iterates; this one-iterate form
        # serves the tenant-batched engine's residual (local mode only: the
        # distributed fast path skips metrics, and the HLO pass verifies its
        # lowering is transpose-free).
        w, alpha = carry
        xtw = (self.operand.full_matvec(w) if self.X is None
               # contract: allow-transpose -- metric on the raw X
               else self.X.T @ w)
        return self._metrics_at(xtw, w, alpha)

    def metrics_stacked(self, ws, alphas):
        """``metrics`` of the s_k iterates of one outer step, rows of ws
        (s_k, d) and alphas (s_k, n), each series of length s_k.  X^T w_j
        for every j comes from ONE contraction over the operand, so the
        history reads X once per outer step instead of s_k times; each
        metric is still evaluated from its own iterate."""
        xtws = (self.operand.full_matmat(ws) if self.X is None
                else jnp.matmul(ws, self.X,
                                precision=jax.lax.Precision.HIGHEST))
        return jax.vmap(self._metrics_at)(xtws, ws, alphas)

    def _metrics_at(self, xtw, w, alpha):
        n = self.n
        r = xtw - self.y
        m = {"objective": 0.5 / n * (r @ r) + 0.5 * self.lam * (w @ w),
             # ||X^T w - alpha - y|| -> 0 at the dual optimum (alpha tracks
             # the primal residual X^T w - y), so unlike the primal's proxy
             # this one IS a convergence residual; local mode only (uses X).
             "residual": jnp.linalg.norm(r - alpha)
             / (n * (1.0 + jnp.linalg.norm(self.y)))}
        if self.w_ref is not None:
            m["sol_err"] = _sol_err(w, self.w_ref)
        return m


class DualRidge:
    """(CA-)BDCD: samples data points (columns of X) through a column-major
    operand -- X^T in row slabs when bound once, the original (dl, n) shard
    gathered in place inside the sharded body; 1D-block-row layout."""
    name = "dual"
    operand_layout = "cols"

    def contracts(self):
        # Theorem 2/7 structure, plus the PR-5 guarantee this formulation
        # exists to keep: the ORIGINAL (d, n) layout is never transposed
        # anywhere in the sharded solve body.  Guard mode keeps both: the
        # health word rides the one packet all-reduce.  The Gram scale
        # 1/(lam n^2) is per-tenant, so the batched engine contracts the
        # RAW Gram once and scales it per tenant post-reduce.
        return SolverContracts(health_in_packet=True, tenant_batched=True)

    def sample_dim(self, d, n):
        return n

    def tenant_constants(self, lam: float, d: int, n: int) -> dict:
        # Host-exact per-tenant derived constants for the batched engine:
        # computed in f64 python arithmetic from a concrete lam (exactly as
        # the single solve's properties do) and pinned on the bound, so the
        # traced per-tenant lam never rounds an intermediate to f32.
        return {"scale_c": 1.0 / (lam * n * n), "lam_n": lam * n}

    def bind(self, X, y, lam, *, x0=None, w_ref=None):
        # Built once: X^T in row slabs, one transposed copy made here (or
        # by the caller, which passes a prebuilt ColMajorOperand).  A
        # prebuilt operand serves init and metrics itself; a raw X is kept
        # for them (one extra resident copy of the dataset).
        raw = None if isinstance(X, ColMajorOperand) else X
        return _BoundDual(operand=ColMajorOperand.of(X), y=y, lam=lam,
                          n=X.shape[1], X=raw, alpha0=x0, w_ref=w_ref)

    def pad_shards(self, X, y, n_shards):
        return _pad_to(X, n_shards, 0), y

    def bind_shard(self, Xl, yl, lam, *, d, n, x0=None):
        # Built inside the sharded body on every call, where the operand
        # may not be transposed (``operand_transpose_free``): the ORIGINAL
        # (dl, n) shard, padded, its sampled columns gathered in place by
        # the column-tile kernels.
        return _BoundDual(operand=_ColGatherOperand(Xl), y=yl, lam=lam, n=n,
                          alpha0=x0)

    def dist_in_specs(self, axis):
        return P(axis, None), P(None), P(None)

    def dist_out_specs(self, axis):
        return P(axis), P(None)

    def dist_finalize(self, w, alpha, d, n):
        return w[:d], alpha


FORMULATIONS: dict[str, Formulation] = {
    "primal": PrimalRidge(),
    "dual": DualRidge(),
}


def register_formulation(form: Formulation) -> Formulation:
    """Publish a Formulation under its ``name`` so the string-keyed entry
    points (``s_step_solve(\"proximal\", ...)``, ``lower_solver``, the
    benchmark harness) can resolve it.  New formulations call this next to
    their ``register_solver`` entries (e.g. ``repro.core.proximal``)."""
    FORMULATIONS[form.name] = form
    return form


# --------------------------------------------------------------------------
# Engine phases
# --------------------------------------------------------------------------

def _phase(name: str):
    """The named scope of one phase of the s-step loop.  Every op traced
    under it carries ``engine.<name>`` in its HLO ``op_name``, so a device
    profile of the compiled solve can be split by phase
    (:func:`repro.core.hlo_analysis.engine_phase_map`); the scope is metadata
    and changes nothing in the compiled program.  ``loop`` is the outer
    scan's own work: slicing each step's index block, its step counter and
    stacking the per-step history."""
    assert name in ENGINE_PHASES, name
    return jax.named_scope(PHASE_PREFIX + name)


def _overlap(flat, dtype):
    """The duplicate-index overlap matrix of an outer step (assembly)."""
    with _phase("assemble"):
        return overlap_matrix(flat).astype(dtype)     # shared-seed trick


# --------------------------------------------------------------------------
# The communication point
# --------------------------------------------------------------------------

def _axes(axis) -> tuple:
    return axis if isinstance(axis, tuple) else (axis,)


def psum_variadic(leaves, axis):
    """ONE all-reduce for any list of same-dtype arrays: ravel, concatenate,
    psum, split.  This is the explicit variadic packet: XLA builds without
    the all-reduce combiner would otherwise emit one op per array (the
    ROADMAP's 2-all-reduces-per-iteration artifact), which breaks the
    latency accounting the collective-count tests pin down."""
    with _phase("reduce"):
        shapes = [x.shape for x in leaves]
        flat = jnp.concatenate([x.ravel() for x in leaves])
        red = jax.lax.psum(flat, axis)
        out, off = [], 0
        for sh in shapes:
            size = math.prod(sh)
            out.append(red[off:off + size].reshape(sh))
            off += size
        return out


def ring_hops(axis_sizes, law: tuple = (2, -2)) -> int:
    """Collective-permute ops per sync of the ring wire: the affine law
    ``sum_i (a * P_i + c)`` a formulation declares via
    ``SolverContracts.pipelined_hops``.  The default ``(2, -2)`` is the
    two-phase ring's ``2 (P_i - 1)`` hops per mesh axis (a reduce-scatter
    and an all-gather round of ``P_i - 1`` hops each); size-1 axes
    contribute zero hops under that law, matching the implementation's
    skip."""
    a, c = law
    return sum(a * p + c for p in axis_sizes)


def _ring_reduce_scatter(flat, name, P):
    """Phase one of the ring: ``P - 1`` ``ppermute`` hops of one chunk each,
    accumulating around the ring.  After the last hop this shard owns the
    fully-reduced chunk ``(me + 1) % P``.  Every chunk ``j`` is summed along
    ONE fixed chain (shard j's value, then j+1's, then j+2's, ...) no matter
    which shard ends up owning it, so the reduced chunks are deterministic
    bytes -- the property phase two turns into replicated-carry consistency."""
    pad = (-flat.shape[0]) % P
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    buf = flat.reshape(P, flat.shape[0] // P)
    me = jax.lax.axis_index(name)
    perm = [(i, (i + 1) % P) for i in range(P)]
    for t in range(P - 1):
        send = jnp.take(buf, (me - t) % P, axis=0)
        recv = jax.lax.ppermute(send, name, perm)
        buf = buf.at[(me - t - 1) % P].add(recv)
    return buf, me


def _ring_all_gather(buf, me, name, P):
    """Phase two: circulate the reduced chunks another ``P - 1`` hops.
    Received chunks are stored VERBATIM (no arithmetic), so every shard ends
    holding the same bytes phase one produced -- replicated carries stay
    replicated without a psum."""
    perm = [(i, (i + 1) % P) for i in range(P)]
    for t in range(P - 1):
        send = jnp.take(buf, (me + 1 - t) % P, axis=0)
        recv = jax.lax.ppermute(send, name, perm)
        buf = buf.at[(me - t) % P].set(recv)
    return buf.reshape(-1)


def ring_reduce_variadic(leaves, axis, axis_sizes, overlap_fn=None):
    """The pipelined wire: the SAME variadic packet as :func:`psum_variadic`,
    reduced by a two-phase ring of ``ppermute`` hops per mesh axis instead of
    one monolithic psum -- ``2 (P_i - 1)`` collective-permutes per axis,
    each moving a ``1/P_i`` chunk, with NO all-reduce anywhere.

    ``overlap_fn`` (nullary) is the software-pipelining hook: it is invoked
    between the first ring's reduce-scatter and all-gather phases, and its
    result is returned alongside the reduced leaves.  The hook's compute has
    ZERO data dependence on the in-flight reduction (the pipelined driver
    passes the NEXT outer step's Gram contraction, which depends only on the
    index stream), which is what frees a latency-hiding scheduler to run it
    under the hops -- the overlap ``cost_model.overlap_ratio`` accounts.

    Numerics: each chunk is summed along one fixed ring chain and broadcast
    verbatim, so all shards hold IDENTICAL reduced bytes (replicated carries
    stay replicated), but the association differs from psum's tree -- equal
    to the psum wire to f64 ~1e-12, not bit-for-bit.
    """
    with _phase("reduce"):
        shapes = [x.shape for x in leaves]
        flat = jnp.concatenate([x.ravel() for x in leaves])
        size = flat.shape[0]
        extra = None
        for name, P in zip(_axes(axis), axis_sizes):
            if P == 1:
                continue
            buf, me = _ring_reduce_scatter(flat, name, P)
            if extra is None and overlap_fn is not None:
                extra = overlap_fn()
            flat = _ring_all_gather(buf, me, name, P)[:size]
        if extra is None and overlap_fn is not None:
            extra = overlap_fn()    # degenerate all-size-1 mesh: no hops
        out, off = [], 0
        for sh in shapes:
            sz = math.prod(sh)
            out.append(flat[off:off + sz].reshape(sh))
            off += sz
        return out, extra


def _packet_reduce(G_local, r_local, axis, fuse, health=None):
    """THE sync point: one all-reduce per outer iteration, either as the
    fused sb x (sb+1) Gram||residual operand (``fuse_packet=True``, ours) or
    as the explicit variadic packet of the two separate operands
    (``fuse_packet=False``, the paper's two logical reductions packed into
    one wire message).

    Guard mode hands in the per-shard ``health`` word, which rides the SAME
    wire message through the variadic packet regardless of ``fuse`` -- the
    sharded health guards add ZERO extra collectives (the ``health_in_packet``
    contract, statically verified by the analysis sweep).  Returns
    ``(G, r, health)`` with ``health=None`` when no word was handed in.
    """
    if axis is None:
        return G_local, r_local, health
    if health is not None:
        G, r, h = psum_variadic([G_local, r_local, health], axis)
        return G, r, h
    if fuse:
        with _phase("reduce"):
            sb = G_local.shape[0]
            packet = jax.lax.psum(
                jnp.concatenate([G_local, r_local[:, None]], axis=1), axis)
            return packet[:, :sb], packet[:, sb], None
    G, r = psum_variadic([G_local, r_local], axis)
    return G, r, None


def _shard_map(body, mesh, in_specs, out_specs, plan: SolverPlan):
    """``jax.shard_map`` with varying-axis checking on wherever the types
    can state the truth.  Two cases cannot: the ring wire's replicated
    carries are identical on every shard by construction (each reduced
    chunk is broadcast verbatim) but typed as varying, and the Pallas
    interpreter's discharged DMAs drop their buffers' varying axes."""
    check = plan.wire == "psum" and plan.impl != "pallas_interpret"
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)


def _pad_to(x: jax.Array, mult: int, axis: int) -> jax.Array:
    """Zero-pad ``axis`` of x up to a multiple of ``mult``.  Zero rows/columns
    of X contribute nothing to Grams, residuals or updates, and the sampler
    only draws indices < the true size, so padding is exact (tested)."""
    return _pad_axis(x, mult, axis)


# --------------------------------------------------------------------------
# In-scan health guards (DESIGN.md section 7)
# --------------------------------------------------------------------------

# Guard-trip reason bits (``SolveResult.metrics["guard_first_reason"]``).
GUARD_NONFINITE = 1    # NaN/Inf in the packet or the solver carry
GUARD_SHARD_LOSS = 2   # a shard's presence flag missing from the reduction
GUARD_DIVERGENCE = 4   # packet-vector norm blew past its running envelope
GUARD_MAGNITUDE = 8    # packet magnitude blew past its envelope (bit flips)
GUARD_COND = 16        # Gram-diagonal condition proxy tripped
GUARD_BREAKDOWN = 32   # the inner sweep itself produced nonfinite updates

_HEALTH_WORDS = 5


class GuardState(NamedTuple):
    """Replicated guard telemetry threaded through the outer scan.  The
    envelopes are running minima of ``1 + ||u||^2`` / ``1 + max|G_local|``
    (the +1 floors them so an iterate growing from exactly zero -- the dual's
    cold-started w -- cannot arm a zero envelope); divergence/magnitude
    guards therefore need one clean outer step to arm."""
    env_r: jax.Array        # running floor of 1 + packet-vector norm^2
    env_g: jax.Array        # running floor of 1 + max |G_local|
    trips: jax.Array        # int32 count of tripped outer steps
    first_trip: jax.Array   # int32 outer index of the first trip (-1: clean)
    first_reason: jax.Array  # int32 GUARD_* bitmask at the first trip
    max_jitter: jax.Array   # largest diagonal jitter applied by a rescue


def _guard_init(dtype) -> GuardState:
    inf = jnp.asarray(jnp.inf, dtype)
    return GuardState(inf, inf, jnp.zeros((), jnp.int32),
                      jnp.full((), -1, jnp.int32), jnp.zeros((), jnp.int32),
                      jnp.zeros((), dtype))


def _guard_metrics(gstate: GuardState) -> dict:
    return {"guard_trips": gstate.trips,
            "guard_first_trip": gstate.first_trip,
            "guard_first_reason": gstate.first_reason,
            "guard_max_jitter": gstate.max_jitter}


def _health_local(Gl, rl, carry, u, dtype):
    """The per-shard health word (length ``_HEALTH_WORDS``) that rides the
    packet psum: [nonfinite count in (G, r); nonfinite count in the carry;
    local packet-vector squared norm; shard presence; max |G_local|].  All
    entries are sums, so ONE psum yields the global verdicts."""
    nonfinite = ((~jnp.isfinite(Gl)).sum()
                 + (~jnp.isfinite(rl)).sum()).astype(dtype)
    carry_bad = sum(((~jnp.isfinite(leaf)).sum()
                     for leaf in jax.tree.leaves(carry)),
                    jnp.zeros((), jnp.int32)).astype(dtype)
    r2 = jnp.sum(u * u).astype(dtype)
    present = jnp.ones((), dtype)
    gmax = jnp.max(jnp.abs(Gl)).astype(dtype)
    return jnp.stack([nonfinite, carry_bad, r2, present, gmax])


def _guarded_sweep(bound, plan, A, base, s_k, b, flat, carry, O, h, gstate,
                   step, n_shards, dtype):
    """Check the reduced health word, then solve -- degrading instead of
    corrupting.  Every decision derives from the replicated post-psum word
    (plus the replicated A / dxs), so all shards branch identically.

    The degradation ladder's first rung lives here: nonfinite packets,
    missing shards and bit-flip-scale magnitudes SKIP the update (dxs = 0 --
    one outer step of progress lost, carry untouched); divergence, the
    condition proxy and an inner-sweep breakdown RESCUE it (sanitize, pick
    the smallest working diagonal jitter, re-sweep).  Rung two (the s=1
    tail) is driver-level; rung three (restart) is the supervisor's.
    """
    i32 = jnp.int32
    boost = jnp.asarray(plan.guard_boost, dtype)
    one = jnp.asarray(1.0, dtype)
    bad_nonfinite = (h[0] + h[1]) > 0
    bad_shard = h[3] != n_shards
    r_now, g_now = one + h[2], one + h[4]
    bad_div = r_now > boost * gstate.env_r
    bad_mag = g_now > boost * gstate.env_g
    diag = jnp.diagonal(A)
    dmin = jnp.min(diag)
    tiny = jnp.asarray(jnp.finfo(dtype).tiny, dtype)
    cond_max = (plan.guard_cond_max if plan.guard_cond_max is not None
                else 0.1 / float(jnp.finfo(dtype).eps))
    bad_cond = (dmin <= 0) | (
        jnp.max(diag) / jnp.maximum(dmin, tiny) > cond_max)
    skip = bad_nonfinite | bad_shard | bad_mag
    dxs = bound.inner_sweep(A, base, s_k, b, flat, carry, O)
    bad_solve = ~jnp.all(jnp.isfinite(dxs))
    rescue = (bad_div | bad_cond | bad_solve) & ~skip

    def _rescue(_):
        As = jnp.nan_to_num(A, nan=0.0, posinf=0.0, neginf=0.0)
        bs = jnp.nan_to_num(base, nan=0.0, posinf=0.0, neginf=0.0)
        jitter, _ok = choose_jitter(As)
        eye = jnp.eye(s_k * b, dtype=dtype)
        dj = bound.inner_sweep(As + jitter * eye, bs, s_k, b, flat, carry, O)
        return jnp.where(jnp.isfinite(dj), dj, jnp.zeros_like(dj)), jitter

    dxs, jitter = jax.lax.cond(
        rescue, _rescue, lambda _: (dxs, jnp.zeros((), dtype)), None)
    dxs = jnp.where(skip, jnp.zeros_like(dxs), dxs)
    tripped = skip | rescue
    reason = (bad_nonfinite.astype(i32) * GUARD_NONFINITE
              + bad_shard.astype(i32) * GUARD_SHARD_LOSS
              + bad_div.astype(i32) * GUARD_DIVERGENCE
              + bad_mag.astype(i32) * GUARD_MAGNITUDE
              + bad_cond.astype(i32) * GUARD_COND
              + bad_solve.astype(i32) * GUARD_BREAKDOWN)
    first = (gstate.first_trip < 0) & tripped
    step_i = jnp.asarray(step, i32)
    gstate = GuardState(
        env_r=jnp.where(jnp.isfinite(r_now),
                        jnp.minimum(gstate.env_r, r_now), gstate.env_r),
        env_g=jnp.where(jnp.isfinite(g_now),
                        jnp.minimum(gstate.env_g, g_now), gstate.env_g),
        trips=gstate.trips + tripped.astype(i32),
        first_trip=jnp.where(first, step_i, gstate.first_trip),
        first_reason=jnp.where(first, reason, gstate.first_reason),
        max_jitter=jnp.maximum(gstate.max_jitter, jitter))
    ginfo = {"guard_tripped": tripped.astype(dtype),
             "guard_reason": reason.astype(dtype),
             "guard_jitter": jitter}
    return dxs, gstate, ginfo


# --------------------------------------------------------------------------
# The one s-step body + driver
# --------------------------------------------------------------------------

def _assemble_subproblem(bound, G0, r, carry, flat, O, sb: int, scale=None):
    """Post-contraction subproblem assembly: ``A = scale*G0 + reg*(I or O)``
    plus the formulation rhs, from the RAW (unscaled, unregularized) Gram.

    This is deliberately the ONE code path both the single and the
    tenant-batched drivers run.  XLA's fma contraction is pattern-local and
    greedy: an identical mul/add graph contracts identically in any compiled
    body, but fusing the scale into the kernel for one driver and
    post-multiplying for the other gives the two drivers different graphs
    whose contraction choices differ -- an ulp apart on the regularized
    entries.  ``optimization_barrier`` does NOT block the contraction on the
    CPU backend (it happens below HLO), so identical graphs, not fences, are
    what keeps the drivers bit-for-bit.  ``O`` is the duplicate-index
    overlap matrix (diagonal exactly 1) or ``None`` for the local s_k=1
    step, whose only regularized entries are the diagonal.

    ``scale`` overrides ``bound.scale`` -- the batched driver passes a
    per-tenant TRACED scalar even when the value is tenant-independent
    (primal/proximal's 1/n).  A loop-invariant ``scale*G0`` gets hoisted
    out of the tenant ``lax.map`` loop, which parks the mul in a different
    basic block from the ``+ regO`` add and forfeits the fma the single
    driver's straight-line step performs -- the regularized diagonal lands
    an ulp apart.  A traced per-item scale pins the mul inside the loop
    next to the add, restoring the single driver's contraction."""
    with _phase("assemble"):
        dtype = G0.dtype
        # reg*O built as a SELECT on a barriered reg, not a multiply: O is a
        # 0/1 matrix, so the values are identical, but a mul here would compete
        # with scale*G0 for the fma contraction, and a python-float reg (single
        # driver) would constant-fold where a traced per-tenant reg (batched
        # driver) cannot -- either asymmetry leaves the two drivers' compiled
        # assemblies an ulp apart.  The barrier keeps reg a runtime value (that
        # much IS within optimization_barrier's power), so every driver carries
        # the same live select and scale*G0 is the only contractible mul.
        mask = jnp.eye(sb, dtype=bool) if O is None else O != 0
        regO = jnp.where(mask,
                         jax.lax.optimization_barrier(
                             jnp.asarray(bound.reg, dtype)),
                         jnp.zeros((), dtype))
        A = (bound.scale if scale is None else scale) * G0 + regO
        # The residual scale is applied HERE, not in the kernel epilogue: a
        # kernel-fused ``scale_r*acc`` sits in the same compiled body as the
        # formulation rhs and fma-contracts into it (single driver), while a
        # residual that crossed a loop or module boundary (batched driver, any
        # psum) arrives rounded -- an ulp apart on warm iterates.  With r raw
        # from the kernel, both drivers run this same mul-into-rhs seam and
        # contract identically.  scale_r is a static python float (1/n, or the
        # dual's exact 1.0, which folds), so no hoisting hazard arises: the mul
        # partner r is per-tenant/per-step either way.
        scale_r = bound.scale if bound.scale_r is None else bound.scale_r
        return A, bound.base(scale_r * r, carry, flat)


def _outer_step(bound: BoundFormulation, plan: SolverPlan, s_k: int, carry,
                idx_k, *, axis=None, collect=False, step=None, gstate=None,
                n_shards=1):
    """ONE outer iteration of the s-step method -- the repo's only solver hot
    loop.  ``s_k`` is the number of inner blocks this outer iteration carries
    (``plan.s`` normally; ``iters % s`` for the ragged tail).

    Every mode applies the regularizer post-contraction on the replicated
    (or local) Gram -- local adds ``reg*I`` at s_k=1 and ``reg*O`` with the
    duplicate-index overlap terms at s_k>1; distributed reduces the local
    contribution through :func:`_packet_reduce` first and then adds
    ``reg*O`` once on the replicated result.  Keeping reg OUT of the kernel
    keeps all paths (and the tenant-batched driver, whose per-tenant reg can
    never be fused into the one shared contraction) bit-for-bit consistent.

    Guard mode (``plan.guard``): the health word is computed on the local
    contribution (AFTER any injected fault, so injection is detectable),
    rides the one packet reduction, and the sweep runs through
    :func:`_guarded_sweep`.  ``step`` is the outer-iteration index (traced;
    only consumed by guards and fault hooks), ``gstate`` the
    :class:`GuardState` threaded across outer steps, ``n_shards`` the
    expected presence total.
    """
    b = plan.b
    sb = s_k * b
    pp = plan.packet
    dtype = bound.operand.dtype
    flat = idx_k.reshape(sb)
    dist = axis is not None
    with _phase("packet"):
        u = bound.packet_vector(carry)
        # The packet leaves the kernel fully RAW (scale=1, scale_r=1,
        # reg=0): every scale and the regularizer are applied
        # post-contraction by the one shared :func:`_assemble_subproblem`,
        # so the single and tenant-batched drivers run the identical
        # assembly graph (see that helper for why identical graphs -- not
        # fences -- are what keeps them bit-for-bit, and why a kernel-fused
        # scale_r in particular would contract into the rhs here but not in
        # the batched driver).
        Gl, rl = gram_packet_sampled(bound.operand, flat, u,
                                     scale=1.0, scale_r=1.0,
                                     reg=0.0, plan=pp)
        if plan.fault is not None:
            Gl, rl = plan.fault.apply_packet(Gl, rl, step=step, axis=axis)
        health = None
        if plan.guard:
            health = _health_local(Gl, rl, carry, u, dtype)
            if plan.fault is not None:
                health = plan.fault.apply_health(health, step=step, axis=axis)
    G, r, h = _packet_reduce(Gl, rl, axis, plan.fuse_packet, health)
    if dist or s_k > 1:
        O = _overlap(flat, dtype)
    else:
        O = None        # a single block has no cross-block overlap terms
    A, base = _assemble_subproblem(bound, G, r, carry, flat, O, sb)
    with _phase("sweep"):
        if plan.guard:
            dxs, gstate, ginfo = _guarded_sweep(bound, plan, A, base, s_k, b,
                                                flat, carry, O, h, gstate,
                                                step, n_shards, dtype)
        else:
            dxs = bound.inner_sweep(A, base, s_k, b, flat, carry, O)
            ginfo = None

    if not collect:
        # Fast path (distributed): apply all s_k blocks in one deferred
        # update -- sum_j Y_j^T dx_j == Y^T dxs.
        with _phase("update"):
            return bound.update(carry, flat, dxs, pp), gstate, None

    # Metric path: reconstruct the per-inner-iteration trajectory locally.
    # The inner scan exists for the history, so its own work is metrics.  A
    # formulation whose metrics read the whole operand (the dual's X^T w)
    # supplies ``metrics_stacked``: the scan then stacks the s_k iterates
    # (w, alpha), and their metrics come from one pass over the operand.
    stacked = getattr(bound, "metrics_stacked", None)

    def inner(c, j):
        sl = jax.lax.dynamic_slice_in_dim
        with _phase("update"):
            c = bound.update(c, sl(flat, j * b, b), sl(dxs, j * b, b), pp)
        return c, (c[:2] if stacked else bound.metrics(c))

    with _phase("metrics"):
        carry, hist = jax.lax.scan(inner, carry, jnp.arange(s_k))
        if stacked:
            hist = stacked(*hist)
        if plan.track_cond:
            # Fig. 4i conditions the scaled packet with its ridge diagonal
            # (scale*G + reg*I) -- the quantity the kernel used to emit when
            # scale/reg were fused.  The packet now leaves the kernel raw,
            # so rebuild it here; A is NOT it (A's off-diagonal overlap
            # entries shift the spectrum at s > 1).
            Greg = bound.scale * G + bound.reg * jnp.eye(sb, dtype=dtype)
            hist["gram_cond"] = jnp.full((s_k,), jnp.linalg.cond(Greg))
        if ginfo is not None:
            # Guard telemetry broadcast to the inner-iteration grid so it
            # concatenates with the other history series.
            for k, v in ginfo.items():
                hist[k] = jnp.full((s_k,), v)
    return carry, gstate, hist


def _gram_only(operand, flat, pp):
    """The Gram half of the packet for a FUTURE outer step.  ``u = 0`` /
    ``scale_r = 0`` make the fused residual output a don't-care, so this
    runs the same contraction cells as the fused packet's G (the batched
    driver's shared-Gram precedent) -- and, crucially, depends only on the
    index stream, never the solver carry, so the pipelined scan can contract
    step k+1's Gram while step k's reduction is on the wire."""
    with _phase("packet"):
        u0 = jnp.zeros((operand.contraction,), operand.dtype)
        G, _ = gram_packet_sampled(operand, flat, u0, scale=1.0, scale_r=0.0,
                                   reg=0.0, plan=pp)
        return G


def _outer_step_pipelined(bound: BoundFormulation, plan: SolverPlan, s_k: int,
                          carry, Gl, idx_k, flat_next, *, axis, axis_sizes,
                          step=None, gstate=None, n_shards=1):
    """ONE outer iteration on the pipelined wire (``plan.wire == "ring"``).

    ``Gl`` is THIS step's local Gram contribution, contracted one step ahead
    and double-buffered through the scan carry.  The body adds the
    carry-dependent half of the packet (the residual direction, which cannot
    be skewed), puts the whole packet -- Gram, residual, and in guard mode
    the health word, zero extra collectives -- on the decomposed ring
    reduction, and contracts the NEXT step's Gram between the ring's
    reduce-scatter and all-gather phases: the compute the monolithic psum
    would serialize behind the wire.  Fault hooks apply at consumption time,
    exactly where the psum backend applies them, so injection semantics (and
    the guard verdicts they trip) are identical across wires.
    """
    b = plan.b
    sb = s_k * b
    pp = plan.packet
    dtype = bound.operand.dtype
    flat = idx_k.reshape(sb)
    with _phase("packet"):
        u = bound.packet_vector(carry)
        # Same contraction cells as the fused packet's r (the batched
        # driver's panel_matvec precedent); raw like every packet, scales
        # applied by the shared _assemble_subproblem.
        rl = panel_matvec(bound.operand, flat, u, scale=1.0, plan=pp)
        if plan.fault is not None:
            Gl, rl = plan.fault.apply_packet(Gl, rl, step=step, axis=axis)
        leaves = [Gl, rl]
        if plan.guard:
            health = _health_local(Gl, rl, carry, u, dtype)
            if plan.fault is not None:
                health = plan.fault.apply_health(health, step=step, axis=axis)
            leaves.append(health)
    red, Gl_next = ring_reduce_variadic(
        leaves, axis, axis_sizes,
        overlap_fn=lambda: _gram_only(bound.operand, flat_next, pp))
    G, r = red[0], red[1]
    h = red[2] if plan.guard else None
    O = _overlap(flat, dtype)
    A, base = _assemble_subproblem(bound, G, r, carry, flat, O, sb)
    with _phase("sweep"):
        if plan.guard:
            dxs, gstate, _ = _guarded_sweep(bound, plan, A, base, s_k, b,
                                            flat, carry, O, h, gstate, step,
                                            n_shards, dtype)
        else:
            dxs = bound.inner_sweep(A, base, s_k, b, flat, carry, O)
    with _phase("update"):
        return bound.update(carry, flat, dxs, pp), gstate, Gl_next


def _drive_pipelined(bound: BoundFormulation, plan: SolverPlan, idx, *, axis,
                     axis_sizes, n_shards=1, step0=0):
    """The software-pipelined s-step scan (``plan.wire == "ring"``): same
    outer/ragged split as :func:`_drive`, over :func:`_outer_step_pipelined`.

    The skew: the scan carry double-buffers the NEXT outer step's local Gram
    contribution.  A prologue contracts step 0's Gram before the scan; each
    step consumes the carried Gram, rides the ring, and contracts its
    successor's between the ring phases.  The epilogue cost is one discarded
    ``sb x sb`` contraction per scan segment (the last step's ``flat_next``
    is its own indices, standing in for a nonexistent step H+1) -- the
    standard software-pipelining prologue/epilogue shape.  The ragged tail's
    packet has a different width, so it runs its own prologue + length-1
    scan, like :func:`_drive`'s tail and for the same compiled-body reasons.

    History collection is not supported: the pipelined backend exists for
    the metric-free distributed fast path.  Returns ``(carry, {}, gstate)``.
    """
    s, b = plan.s, plan.b
    pp = plan.packet
    iters = idx.shape[0]
    outer_full, rem = divmod(iters, s)
    with _phase("bind"):
        carry = bound.init_carry(axes=_axes(axis))
    gstate = _guard_init(bound.operand.dtype) if plan.guard else None
    if outer_full:
        blocks = idx[:outer_full * s].reshape(outer_full, s, b)
        flats = blocks.reshape(outer_full, s * b)
        with _phase("packet"):
            flats_next = jnp.concatenate([flats[1:], flats[-1:]])
        Gl0 = _gram_only(bound.operand, flats[0], pp)

        def outer(cg, xs):
            step, idx_k, flat_next = xs
            c, g, Gl = _outer_step_pipelined(
                bound, plan, s, cg[0], cg[2], idx_k, flat_next, axis=axis,
                axis_sizes=axis_sizes, step=step, gstate=cg[1],
                n_shards=n_shards)
            return (c, g, Gl), None
        steps = jnp.arange(outer_full, dtype=jnp.int32) + step0
        with _phase("loop"):
            (carry, gstate, _), _ = jax.lax.scan(
                outer, (carry, gstate, Gl0), (steps, blocks, flats_next),
                unroll=plan.unroll)
    if rem:
        flat_t = idx[outer_full * s:].reshape(rem * b)
        Gl_t = _gram_only(bound.operand, flat_t, pp)

        def tail(cg, xs):
            step, idx_k = xs
            c, g, Gl = _outer_step_pipelined(
                bound, plan, rem, cg[0], cg[2], idx_k, flat_t, axis=axis,
                axis_sizes=axis_sizes, step=step, gstate=cg[1],
                n_shards=n_shards)
            return (c, g, Gl), None
        with _phase("loop"):
            (carry, gstate, _), _ = jax.lax.scan(
                tail, (carry, gstate, Gl_t),
                (jnp.asarray([outer_full + step0], jnp.int32),
                 idx[outer_full * s:][None]))
    return carry, {}, gstate


def _resolve_form(formulation) -> "Formulation":
    """Resolve a formulation name (or pass an instance through), pulling in
    the sibling modules that self-register on first use."""
    if not isinstance(formulation, str):
        return formulation
    if formulation not in FORMULATIONS:
        from . import accelerated, bcd, bdcd, distributed, proximal  # noqa: F401
    try:
        return FORMULATIONS[formulation]
    except KeyError:
        raise KeyError(
            f"unknown formulation {formulation!r}; "
            f"available: {sorted(FORMULATIONS)}") from None


def _check_idx(idx, iters: int, b: int) -> None:
    """An explicit index stream must cover exactly the requested iterations
    (the pre-engine CA solvers raised on the mismatch via their reshape; keep
    that contract rather than silently running idx's length)."""
    if idx.shape != (iters, b):
        raise ValueError(
            f"idx shape {idx.shape} does not match (iters, b) = ({iters}, {b})")


def _drive(bound: BoundFormulation, plan: SolverPlan, idx, *, axis=None,
           collect=True, n_shards=1, step0=0, axis_sizes=None):
    """The engine's s-step scan: ``iters // s`` outer iterations through ONE
    ``lax.scan`` over :func:`_outer_step`, plus (when ``iters % s != 0``) a
    single ragged call of the same body with ``s_k = iters % s``.

    ``step0`` offsets the outer-iteration indices handed to the guard/fault
    hooks, so a segmented solve (the supervisor's checkpointed resume) keeps
    globally meaningful step numbers.  Returns ``(carry, history, gstate)``
    with ``gstate=None`` when guards are off.

    ``plan.wire == "ring"`` (distributed only; ``axis_sizes`` carries the
    static mesh axis sizes the ring needs) reroutes to the software-
    pipelined driver :func:`_drive_pipelined`.
    """
    if plan.wire == "ring" and axis is not None:
        return _drive_pipelined(bound, plan, idx, axis=axis,
                                axis_sizes=axis_sizes, n_shards=n_shards,
                                step0=step0)
    s, b = plan.s, plan.b
    iters = idx.shape[0]
    outer_full, rem = divmod(iters, s)
    with _phase("bind"):
        carry = bound.init_carry(axes=None if axis is None else _axes(axis))
    gstate = _guard_init(bound.operand.dtype) if plan.guard else None
    hists = []
    if outer_full:
        def outer(cg, xs):
            step, idx_k = xs
            c, g, hist = _outer_step(bound, plan, s, cg[0], idx_k, axis=axis,
                                     collect=collect, step=step, gstate=cg[1],
                                     n_shards=n_shards)
            return (c, g), hist
        steps = jnp.arange(outer_full, dtype=jnp.int32) + step0
        with _phase("loop"):
            (carry, gstate), hist = jax.lax.scan(
                outer, (carry, gstate),
                (steps, idx[:outer_full * s].reshape(outer_full, s, b)),
                unroll=plan.unroll)
        if collect:
            hists.append({k: v.reshape(outer_full * s, *v.shape[2:])
                          for k, v in hist.items()})
    if rem:
        # The ragged tail runs through a length-1 scan ON PURPOSE: lax.scan
        # compiles its body, so the tail sees the same compiled-body fma
        # contraction as the full steps and the batched driver's per-tenant
        # lax.map -- an eager tail would round the assembly seams
        # differently (see _assemble_subproblem).
        def tail(cg, xs):
            step, idx_k = xs
            c, g, hist = _outer_step(bound, plan, rem, cg[0], idx_k,
                                     axis=axis, collect=collect, step=step,
                                     gstate=cg[1], n_shards=n_shards)
            return (c, g), hist
        with _phase("loop"):
            (carry, gstate), hist = jax.lax.scan(
                tail, (carry, gstate),
                (jnp.asarray([outer_full + step0], jnp.int32),
                 idx[outer_full * s:][None]))
        if collect:
            hists.append({k: v.reshape(rem, *v.shape[2:])
                          for k, v in hist.items()})
    if len(hists) > 1:
        with _phase("metrics"):
            history = {k: jnp.concatenate([h[k] for h in hists])
                       for k in hists[0]}
    else:
        history = hists[0] if hists else {}
    return carry, history, gstate


def s_step_solve(formulation: Formulation | str, plan: SolverPlan,
                 X: jax.Array, y: jax.Array, lam: float, iters: int,
                 key: jax.Array | None = None, *, x0: jax.Array | None = None,
                 idx: jax.Array | None = None,
                 w_ref: jax.Array | None = None, step0: int = 0) -> SolveResult:
    """Single-device s-step solve.  ``plan.s == 1`` IS the classical variant;
    larger ``s`` trades bandwidth for latency without changing the iterates
    (the paper's central claim, preserved per-formulation by construction).

    ``x0`` warm-starts the formulation's own iterate (w for primal, alpha for
    dual).  ``idx`` overrides the sampled index stream -- the classical and
    CA runs that share it produce identical iterates in exact arithmetic.
    ``step0`` offsets the guard/fault outer-step numbering (segmented solves).

    With ``plan.guard`` the result's ``metrics`` carry the guard telemetry,
    and a trip at ``s > 1`` engages rung two of the degradation ladder: the
    clean prefix is replayed at ``s``, the remaining iterations run at
    ``s = 1`` so any further breakdown poisons one iteration instead of
    ``s`` (eager calls only -- under ``jit`` the ladder is skipped and the
    in-scan recovery of rung one is the whole story).
    """
    form = _resolve_form(formulation)
    if plan.wire != "psum":
        raise ValueError(
            f"SolverPlan.wire={plan.wire!r} needs a distributed backend; "
            "the local solve has no reduction to decompose")
    d, n = X.shape
    if idx is None:
        idx = sample_blocks(key, form.sample_dim(d, n), plan.b, iters)
    else:
        _check_idx(idx, iters, plan.b)
    with _phase("bind"):
        bound = form.bind(X, y, lam, x0=x0, w_ref=w_ref)
    # Generic carry unpack: formulations may carry extra scan state beyond
    # (w, alpha) -- the accelerated formulation's velocity rides at [2:].
    carry, history, gstate = _drive(bound, plan, idx, step0=step0)
    w, alpha = carry[0], carry[1]
    metrics = {}
    if plan.guard:
        metrics = _guard_metrics(gstate)
        if plan.s > 1 and not isinstance(gstate.first_trip, jax.core.Tracer):
            first = int(jax.device_get(gstate.first_trip))
            if first >= 0:
                return _degrade_to_s1_tail(form, plan, X, y, lam, idx, first,
                                           step0, x0, w_ref, metrics)
    return SolveResult(w, alpha, history, metrics)


def _degrade_to_s1_tail(form, plan, X, y, lam, idx, first, step0, x0, w_ref,
                        metrics):
    """Degradation ladder, rung two (driver-level): a guard tripped at outer
    step ``first`` of an ``s > 1`` solve.  Replay the clean prefix at the
    original ``s`` (deterministic: the same index stream over the same data
    reproduces the same clean steps), warm-start from its iterate, and run
    the remaining iterations at ``s = 1`` -- further breakdowns now poison a
    single iteration's deferred update instead of ``s`` of them.  The tail
    keeps the guard (and any injected fault, remapped to fire at its outer
    step) so recovery is exercised, not dodged."""
    n_clean = (first - step0) * plan.s
    hists = []
    if n_clean > 0:
        pre = s_step_solve(form, plan, X, y, lam, n_clean, None, x0=x0,
                           idx=idx[:n_clean], w_ref=w_ref, step0=step0)
        hists.append(pre.history)
        x0 = pre.w if form.operand_layout == "rows" else pre.alpha
    tail_plan = dataclasses.replace(plan, s=1)
    tail = s_step_solve(form, tail_plan, X, y, lam, idx.shape[0] - n_clean,
                        None, x0=x0, idx=idx[n_clean:], w_ref=w_ref,
                        step0=first)
    if hists:
        history = {k: jnp.concatenate([h[k] for h in hists + [tail.history]])
                   for k in tail.history}
    else:
        history = tail.history
    metrics = dict(metrics)
    metrics["s1_tail_from_outer"] = first
    metrics["s1_tail_from_iter"] = n_clean
    metrics["s1_tail_trips"] = tail.metrics["guard_trips"]
    metrics["guard_max_jitter"] = jnp.maximum(
        metrics["guard_max_jitter"], tail.metrics["guard_max_jitter"])
    return SolveResult(tail.w, tail.alpha, history, metrics)


def s_step_solve_sharded(formulation: Formulation | str, plan: SolverPlan,
                         mesh: Mesh, X: jax.Array, y: jax.Array, lam: float,
                         iters: int, key: jax.Array | None = None, *,
                         axis="shards", idx: jax.Array | None = None,
                         x0: jax.Array | None = None, step0: int = 0):
    """Distributed s-step solve: the SAME driver as :func:`s_step_solve`,
    wrapped in ``shard_map`` with the formulation's 1D layout.  The only
    behavioural differences are the inserted packet all-reduce (one per outer
    iteration) and the skipped metric reconstruction: the sharded solve takes
    the fast-path update (all ``s * b`` sampled rows of an outer step applied
    in one deferred call, not block by block) and returns no per-iteration
    history, so no ``engine.metrics`` phase runs.  Returns ``(w, alpha)``
    with the formulation's output sharding -- or ``(w, alpha, metrics)`` when
    ``plan.guard`` is set (the replicated guard telemetry, same keys as the
    local solve's ``SolveResult.metrics``).

    ``x0`` warm-starts the formulation's own replicated iterate (w for the
    primal family, alpha for the dual); the device-varying half of the carry
    is re-derived shard-locally (see the formulations' ``init_carry``), which
    is what the supervisor's checkpointed elastic restart rides.
    """
    form = _resolve_form(formulation)
    d, n = X.shape
    if idx is None:
        idx = sample_blocks(key, form.sample_dim(d, n), plan.b, iters)
    else:
        _check_idx(idx, iters, plan.b)
    axis_sizes = tuple(mesh.shape[a] for a in _axes(axis))
    n_shards = math.prod(axis_sizes)
    with _phase("bind"):
        X, y = form.pad_shards(X, y, n_shards)
    has_x0 = x0 is not None

    def body(Xl, yl, idx_rep, *x0_rep):
        kw = {"x0": x0_rep[0]} if has_x0 else {}
        with _phase("bind"):
            # The shard-local operand layout (the row slabs' relayout of
            # Xl) runs here, inside every call.
            bound = form.bind_shard(Xl, yl, lam, d=d, n=n, **kw)
        carry, _, gstate = _drive(bound, plan, idx_rep, axis=axis,
                                  collect=False, n_shards=n_shards,
                                  step0=step0, axis_sizes=axis_sizes)
        return (carry, gstate) if plan.guard else carry

    in_specs = form.dist_in_specs(axis) + ((P(None),) if has_x0 else ())
    out_specs = form.dist_out_specs(axis)
    if plan.guard:
        out_specs = (out_specs, GuardState(*(P(),) * len(GuardState._fields)))
    fn = _shard_map(body, mesh, in_specs, out_specs, plan)
    args = (X, y, idx) + ((x0,) if has_x0 else ())
    # Generic carry unpack, like s_step_solve: extra carry leaves (the
    # accelerated velocity) ride at [2:] and are dropped by dist_finalize.
    if plan.guard:
        carry, gstate = fn(*args)
        with _phase("bind"):
            w, alpha = form.dist_finalize(carry[0], carry[1], d, n)
        return w, alpha, _guard_metrics(gstate)
    carry = fn(*args)
    with _phase("bind"):
        return form.dist_finalize(carry[0], carry[1], d, n)


# --------------------------------------------------------------------------
# Batched multi-tenant engine: one scan, one psum, T solves (DESIGN.md §8)
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TenantBatch:
    """T tenant solves sharing ONE operand and ONE block-index stream.

    Production traffic is many small solves over the same dataset --
    personalization heads, a lambda path, CV folds -- so the batched engine
    carries a tenant axis through the s-step scan: the sb x sb Gram packet
    (and, sharded, its single psum) is computed once per outer step and
    reused by every tenant, while everything tenant-specific lives here.

    * ``ys`` (T, n): per-tenant targets (the dual's per-tenant Y row).
    * ``lams`` (T,): per-tenant l2 weights.
    * ``coeffs``: extra per-tenant bound-formulation fields, name ->
      (T,)-leading array (e.g. the proximal ``lam1``); applied by
      ``dataclasses.replace`` on the per-tenant bound under ``vmap``.
    * ``x0s`` (T, dim): optional per-tenant warm starts (the formulation's
      own iterate, like the single solve's ``x0``).
    * ``tol``: optional early-retirement threshold on the formulation's
      ``residual`` metric -- a tenant whose residual drops to ``tol`` or
      below has its subsequent updates masked to zero (frozen iterate,
      fixed compiled shapes).  Local backend only: the residual is carry
      state there, while sharded it would cost a second collective.
    """
    ys: jax.Array
    lams: jax.Array
    coeffs: dict = dataclasses.field(default_factory=dict)
    x0s: jax.Array | None = None
    tol: float | None = None

    def __post_init__(self):
        if self.ys.ndim != 2:
            raise ValueError(
                f"TenantBatch.ys must be (tenants, n), got {self.ys.shape}")
        T = self.ys.shape[0]
        if self.lams.shape != (T,):
            raise ValueError(
                f"TenantBatch.lams shape {self.lams.shape} != ({T},)")
        for name, v in self.coeffs.items():
            if v.shape[:1] != (T,):
                raise ValueError(
                    f"TenantBatch.coeffs[{name!r}] must lead with the "
                    f"tenant axis ({T},), got shape {v.shape}")
        if self.x0s is not None and self.x0s.shape[0] != T:
            raise ValueError(
                f"TenantBatch.x0s leads with {self.x0s.shape[0]} != {T}")
        if self.tol is not None and not self.tol > 0:
            raise ValueError(f"TenantBatch.tol={self.tol!r} must be > 0")

    @property
    def tenants(self) -> int:
        return self.ys.shape[0]


class BatchedSolveResult(NamedTuple):
    ws: jax.Array         # (T, d) per-tenant primal iterates
    alphas: jax.Array     # (T, n) per-tenant auxiliary iterates
    active: jax.Array     # (T,) bool: False once a tenant retired early
    metrics: dict = {}


@dataclasses.dataclass
class _BatchedSpec:
    """Everything the batched hot loop closes over: the shared operand and
    the per-tenant data.  The packet runs fully RAW (Gram scale 1, residual
    scale 1, reg 0) and every tenant applies its own scales through the
    shared :func:`_assemble_subproblem`, exactly like the single driver.
    ``scales`` carries the per-tenant Gram scale as a TRACED (T,) array
    when the formulation's scale is a tenant-independent python float --
    a loop-invariant ``scale*G0`` would be hoisted out of the tenant map
    and lose the single driver's fma (see :func:`_assemble_subproblem`);
    ``None`` means ``bound.scale`` is already per-tenant traced (the
    dual's pinned ``scale_c``) and is used directly."""
    form: object
    bind: Callable            # (y_t, lam_t, coeffs_t[, x0_t]) -> bound
    operand: PacketOperand
    ys: jax.Array
    lams: jax.Array
    coeffs: dict
    scales: jax.Array | None
    tol: float | None
    per_block: bool           # local per-block schedule vs one deferred update
    masked: bool              # thread/apply the active mask at all


def _pin_tenant_constants(form, batch: TenantBatch, d: int, n: int,
                          dtype) -> TenantBatch:
    """Pin host-exact derived constants (``Formulation.tenant_constants``)
    into ``batch.coeffs``.  A bound formulation built from a python-float
    lam computes its derived scalars (the dual's 1/(lam n^2) Gram scale and
    lam*n divisor) in f64 host arithmetic; a traced per-tenant lam would
    round each intermediate to f32 and land an ulp off the single solve.
    With concrete lams we replay the host arithmetic per tenant and ship the
    results as per-tenant coeffs; traced lams (jitted callers) fall back to
    in-graph arithmetic -- correct, just not bit-pinned."""
    tc = getattr(form, "tenant_constants", None)
    if tc is None:
        return batch
    try:
        lams = np.asarray(batch.lams)
    except (jax.errors.ConcretizationTypeError,
            jax.errors.TracerArrayConversionError):
        return batch
    consts = [tc(float(lam), d, n) for lam in lams]
    extra = {k: jnp.asarray([c[k] for c in consts], dtype)
             for k in consts[0]}
    return dataclasses.replace(batch, coeffs={**batch.coeffs, **extra})


def _make_batched_spec(form, batch: TenantBatch, bind_one: Callable, *,
                       per_block: bool, masked: bool) -> _BatchedSpec:
    def bind(y_t, lam_t, coeffs_t, x0_t=None):
        with _phase("bind"):
            bound = bind_one(y_t, lam_t, x0_t)
        return dataclasses.replace(bound, **coeffs_t) if coeffs_t else bound

    # Probe with an ARRAY-typed lam so tenant-dependent properties (the
    # dual's traced Gram scale) reveal themselves as non-floats.
    probe = bind(batch.ys[0], jnp.asarray(batch.lams[0]),
                 {k: v[0] for k, v in batch.coeffs.items()})
    scales = None
    if isinstance(probe.scale, (int, float)):
        # Tenant-independent Gram scale: ship it as a traced per-tenant
        # array anyway, or XLA hoists scale*G0 out of the tenant loop and
        # the assembly loses its fma (see _assemble_subproblem).
        with _phase("bind"):
            scales = jnp.full((batch.tenants,), float(probe.scale),
                              batch.ys.dtype)
    return _BatchedSpec(
        form=form, bind=bind, operand=probe.operand, ys=batch.ys,
        lams=batch.lams, coeffs=batch.coeffs, scales=scales,
        tol=batch.tol, per_block=per_block, masked=masked)


def _init_batched(spec: _BatchedSpec, batch: TenantBatch, axes):
    # lax.map, not vmap: each tenant's init then lowers exactly like the
    # single solve's (warm starts included), keeping resumes bit-for-bit.
    def init(args):
        return spec.bind(*args).init_carry(axes=axes)

    xs = (batch.ys, batch.lams, batch.coeffs)
    with _phase("bind"):
        return jax.lax.map(init, xs if batch.x0s is None
                           else xs + (batch.x0s,))


def _outer_step_batched(spec: _BatchedSpec, plan: SolverPlan, s_k: int, state,
                        idx_k, *, axis=None, axis_sizes=None):
    """ONE batched outer iteration.  The sb x sb Gram contraction -- and, in
    distributed mode, its single psum -- happens ONCE and is reused by every
    tenant; only the per-tenant residual directions (T, sb) ride along, so
    the wire payload is sb^2 + T*sb words with the Gram part INDEPENDENT of
    T (the shared-packet invariant the analysis sweep pins down).

    Per-tenant math reproduces the single solve exactly: the regularizer is
    applied post-reduce per tenant (``(g + reg) + 0.0 == g + reg`` and
    ``reg * 1.0 == reg``, so the assembled subproblem matrix equals the
    single solve's under ``==``), the residual directions run through the
    SAME contraction cells as the fused packet's r, and the local schedule
    replays the single solve's per-block inner updates.
    """
    b = plan.b
    sb = s_k * b
    pp = plan.packet
    carries, active = state
    dtype = spec.operand.dtype
    flat = idx_k.reshape(sb)
    dist = axis is not None

    # Shared RAW Gram (scale=1, reg=0): both are per-tenant and are applied
    # by the same _assemble_subproblem the single driver runs, which is what
    # keeps the two drivers' assembly graphs -- and their fma contraction --
    # identical.  The fused residual output is a don't-care (u = 0,
    # scale_r = 0): every real residual is per-tenant.
    def _direction(y_t, lam_t, coeffs_t, carry_t):
        # RAW direction (scale=1), like the single driver's raw packet r:
        # scale_r is applied by the shared _assemble_subproblem next to the
        # rhs seam it contracts into.
        u = spec.bind(y_t, lam_t, coeffs_t).packet_vector(carry_t)
        return panel_matvec(spec.operand, flat, u, scale=1.0, plan=pp)

    with _phase("packet"):
        u0 = jnp.zeros((spec.operand.contraction,), dtype)
        G0, _ = gram_packet_sampled(spec.operand, flat, u0, scale=1.0,
                                    scale_r=0.0, reg=0.0, plan=pp)
        R = jax.vmap(_direction)(spec.ys, spec.lams, spec.coeffs, carries)

    if dist:
        # THE sync point, amortized across the tenant axis: one variadic
        # packet moving sb^2 + T*sb words per outer step.  On the ring wire
        # the shared Gram AND every tenant's direction ride the SAME
        # decomposed reduction -- zero extra collectives vs the psum wire,
        # just 2(P_i - 1) permute hops per axis instead of one all-reduce.
        if plan.wire == "ring":
            (G0, R), _ = ring_reduce_variadic([G0, R], axis, axis_sizes)
        else:
            G0, R = psum_variadic([G0, R], axis)

    if dist or s_k > 1:
        O = _overlap(flat, dtype)
    else:
        O = None            # a single block has no cross-block overlap terms

    # spec.scales is None when bound.scale is already per-tenant traced;
    # the dummy lams ride the map xs unused (DCE'd) to keep one structure.
    sc_xs = spec.lams if spec.scales is None else spec.scales

    def _sweep(args):
        y_t, lam_t, coeffs_t, r0_t, carry_t, sc_t = args
        bound = spec.bind(y_t, lam_t, coeffs_t)
        A, base = _assemble_subproblem(
            bound, G0, r0_t, carry_t, flat, O, sb,
            scale=None if spec.scales is None else sc_t)
        with _phase("sweep"):
            return bound.inner_sweep(A, base, s_k, b, flat, carry_t, O)

    # lax.map, NOT vmap: a batched Cholesky/triangular-solve lowers to a
    # different accumulation order than the unbatched one, so vmapping the
    # sweep would break bit-for-bit parity with the single solve (and the
    # barrier pins above have no vmap batching rule at all).  The per-tenant
    # assembly + sweep is O(s^2 b^2) -- noise next to the shared Gram -- so
    # sequencing it costs nothing while every tenant's subproblem runs
    # through the EXACT op sequence the single solve uses.
    with _phase("sweep"):
        dxs_all = jax.lax.map(
            _sweep, (spec.ys, spec.lams, spec.coeffs, R, carries, sc_xs))

    def _apply(args):
        y_t, lam_t, coeffs_t, dxs, carry_t, active_t = args
        bound = spec.bind(y_t, lam_t, coeffs_t)
        if spec.masked:
            # A retired tenant's applied update is zero: the carry freezes
            # while the compiled shapes (and the shared packet) stay put.
            dxs = jnp.where(active_t, dxs, jnp.zeros_like(dxs))
        if spec.per_block:
            # Replay the single local solve's per-block schedule so batched
            # iterates match unbatched ones bit-for-bit.
            def inner(c, j):
                sl = jax.lax.dynamic_slice_in_dim
                return bound.update(c, sl(flat, j * b, b),
                                    sl(dxs, j * b, b), pp), None
            carry_t, _ = jax.lax.scan(inner, carry_t, jnp.arange(s_k))
        else:
            carry_t = bound.update(carry_t, flat, dxs, pp)
        if spec.tol is not None:
            with _phase("metrics"):
                active_t = active_t & (bound.metrics(carry_t)["residual"]
                                       > spec.tol)
        return carry_t, active_t

    # lax.map again: the per-tenant update replays the single solve's exact
    # op sequence (scatter, panel apply, barrier-pinned epilogue) with
    # unbatched lowerings, which a vmap would not guarantee.
    with _phase("update"):
        carries, active = jax.lax.map(
            _apply, (spec.ys, spec.lams, spec.coeffs, dxs_all, carries,
                     active))
    return carries, active


def _drive_batched(spec: _BatchedSpec, plan: SolverPlan, idx, state0, *,
                   axis=None, axis_sizes=None):
    """The batched s-step scan: same outer/ragged split as :func:`_drive`,
    over :func:`_outer_step_batched`."""
    s, b = plan.s, plan.b
    iters = idx.shape[0]
    outer_full, rem = divmod(iters, s)
    state = state0
    if outer_full:
        def outer(st, idx_k):
            return _outer_step_batched(spec, plan, s, st, idx_k, axis=axis,
                                       axis_sizes=axis_sizes), None
        with _phase("loop"):
            state, _ = jax.lax.scan(
                outer, state, idx[:outer_full * s].reshape(outer_full, s, b),
                unroll=plan.unroll)
    if rem:
        # Length-1 scan for the same reason as _drive's tail: the single
        # driver's tail sees a compiled body with a TRACED index stream, and
        # an eager tail here would constant-fold the gathers and round the
        # per-tenant rhs seam differently (see _assemble_subproblem).
        def tail(st, idx_k):
            return _outer_step_batched(spec, plan, rem, st, idx_k, axis=axis,
                                       axis_sizes=axis_sizes), None
        with _phase("loop"):
            state, _ = jax.lax.scan(tail, state, idx[outer_full * s:][None])
    return state


def _check_batched(form, plan: SolverPlan, batch: TenantBatch):
    if not getattr(form.contracts(), "tenant_batched", False):
        raise ValueError(
            f"formulation {form.name!r} does not declare tenant_batched "
            "support (SolverContracts.tenant_batched)")
    for knob in ("guard", "track_cond"):
        if getattr(plan, knob):
            raise ValueError(
                f"batched solves do not support SolverPlan.{knob} yet")
    if plan.fault is not None:
        raise ValueError("batched solves do not support SolverPlan.fault")
    if plan.tenants is not None and plan.tenants != batch.tenants:
        raise ValueError(
            f"SolverPlan.tenants={plan.tenants} != batch width "
            f"{batch.tenants}: a pinned plan is a compile-cache key, pad "
            "the batch to the bucket instead of recompiling")


def s_step_solve_batched(formulation: Formulation | str, plan: SolverPlan,
                         X: jax.Array, batch: TenantBatch, iters: int,
                         key: jax.Array | None = None, *,
                         idx: jax.Array | None = None, carry0=None,
                         active0: jax.Array | None = None
                         ) -> BatchedSolveResult:
    """Single-device batched solve: T tenants, ONE s-step scan, the Gram
    contraction shared.  Iterates equal T independent :func:`s_step_solve`
    runs over the same index stream -- bit-for-bit on matching kernel tiles
    (the dual's per-tenant Gram scale moves post-contraction, exact on the
    ref backend and on single-k-tile kernel launches; see DESIGN.md
    section 8).

    ``carry0`` (a ``(ws, alphas)`` pair) and ``active0`` resume a previous
    batched solve -- the serve front end steps solves in chunks and
    admits/retires tenants between chunks.  With ``batch.tol`` set, tenants
    whose ``residual`` metric reaches the tolerance are masked to no-ops
    for the rest of the solve (``result.active`` reports who was still
    running).  ``plan.guard`` / ``fault`` / ``track_cond`` are not
    supported on the batched path yet.
    """
    form = _resolve_form(formulation)
    _check_batched(form, plan, batch)
    if plan.wire != "psum":
        raise ValueError(
            f"SolverPlan.wire={plan.wire!r} needs a distributed backend; "
            "the local batched solve has no reduction to decompose")
    d, n = X.shape
    if idx is None:
        idx = sample_blocks(key, form.sample_dim(d, n), plan.b, iters)
    else:
        _check_idx(idx, iters, plan.b)

    def bind_one(y_t, lam_t, x0_t):
        kw = {"x0": x0_t} if x0_t is not None else {}
        return form.bind(X, y_t, lam_t, **kw)

    batch = _pin_tenant_constants(form, batch, d, n, X.dtype)
    masked = batch.tol is not None or active0 is not None
    spec = _make_batched_spec(form, batch, bind_one, per_block=True,
                              masked=masked)
    carries = _init_batched(spec, batch, None) if carry0 is None else carry0
    active = (jnp.ones((batch.tenants,), bool) if active0 is None
              else active0)
    (ws, alphas), active = _drive_batched(spec, plan, idx, (carries, active))
    return BatchedSolveResult(ws, alphas, active)


def batched_residuals(formulation: Formulation | str, X: jax.Array,
                      batch: TenantBatch, carries) -> jax.Array:
    """Per-tenant ``residual`` metric of a batched carry ``(ws, alphas)``.

    The serve front end thresholds this between solve chunks to retire
    tenants against their own tolerances (the engine's scalar
    ``TenantBatch.tol`` handles in-chunk masking; per-tenant tolerances are
    a host-side, chunk-granular decision).  Runs each tenant's metric
    through ``lax.map`` like the batched driver, so the statistic matches
    the single solve's bit-for-bit."""
    form = _resolve_form(formulation)
    d, n = X.shape

    def bind_one(y_t, lam_t, x0_t):
        return form.bind(X, y_t, lam_t)

    batch = _pin_tenant_constants(form, batch, d, n, X.dtype)
    spec = _make_batched_spec(form, batch, bind_one, per_block=True,
                              masked=False)

    def one(args):
        y_t, lam_t, coeffs_t, carry_t = args
        return spec.bind(y_t, lam_t, coeffs_t).metrics(carry_t)["residual"]

    with _phase("metrics"):
        return jax.lax.map(one, (spec.ys, spec.lams, spec.coeffs, carries))


def s_step_solve_batched_sharded(formulation: Formulation | str,
                                 plan: SolverPlan, mesh: Mesh, X: jax.Array,
                                 batch: TenantBatch, iters: int,
                                 key: jax.Array | None = None, *,
                                 axis="shards",
                                 idx: jax.Array | None = None
                                 ) -> BatchedSolveResult:
    """Distributed batched solve: the same batched driver under shard_map,
    with the ONE variadic psum per outer step now amortized across T
    tenants -- H = ceil(iters/s) all-reduces for the whole batch, payload
    sb^2 + T*sb words each, the Gram part independent of T (machine-checked
    by the analysis sweep at T in {1, 8, 64}).

    ``batch.tol`` is rejected here: the per-tenant residual is not carry
    state on a shard (the primal's alpha is sharded, the dual's metric
    needs the full X), so in-scan retirement would cost a SECOND collective
    -- the serve front end retires between chunks on the local backend
    instead.  ``result.active`` is therefore all-True.
    """
    form = _resolve_form(formulation)
    _check_batched(form, plan, batch)
    if batch.tol is not None:
        raise ValueError(
            "batched sharded solves do not support TenantBatch.tol: in-scan "
            "retirement would need a second collective per outer step; "
            "retire between chunks on the local backend instead")
    d, n = X.shape
    if idx is None:
        idx = sample_blocks(key, form.sample_dim(d, n), plan.b, iters)
    else:
        _check_idx(idx, iters, plan.b)
    axis_sizes = tuple(mesh.shape[a] for a in _axes(axis))
    n_shards = math.prod(axis_sizes)
    with _phase("bind"):
        Xp, _ = form.pad_shards(X, batch.ys[0], n_shards)
        ysp = jax.vmap(lambda y: form.pad_shards(X, y, n_shards)[1])(
            batch.ys)
    # Pin host-exact derived constants while the lams are still concrete
    # (inside shard_map they are traced and the pin would be skipped).
    batch = _pin_tenant_constants(form, batch, d, n, X.dtype)
    has_x0 = batch.x0s is not None

    def body(Xl, ysl, lams, coeffs, idx_rep, *x0_rep):
        def bind_one(y_t, lam_t, x0_t):
            kw = {"x0": x0_t} if x0_t is not None else {}
            return form.bind_shard(Xl, y_t, lam_t, d=d, n=n, **kw)

        local = dataclasses.replace(
            batch, ys=ysl, lams=lams, coeffs=coeffs,
            x0s=x0_rep[0] if has_x0 else None)
        spec = _make_batched_spec(form, local, bind_one, per_block=False,
                                  masked=False)
        carries = _init_batched(spec, local, _axes(axis))
        active = jnp.ones((local.tenants,), bool)
        state = _drive_batched(spec, plan, idx_rep, (carries, active),
                               axis=axis, axis_sizes=axis_sizes)
        return state[0]

    def widen(p):
        # Prefix the tenant axis (replicated) onto a single-solve spec.
        return P(*((None,) + tuple(p)))

    xspec, yspec, repspec = form.dist_in_specs(axis)
    in_specs = (xspec, widen(yspec), P(None),
                jax.tree.map(lambda _: P(None), batch.coeffs), repspec)
    in_specs += ((P(None),) if has_x0 else ())
    wspec, aspec = form.dist_out_specs(axis)
    out_specs = (widen(wspec), widen(aspec))
    fn = _shard_map(body, mesh, in_specs, out_specs, plan)
    args = (Xp, ysp, batch.lams, batch.coeffs, idx)
    args += ((batch.x0s,) if has_x0 else ())
    ws, alphas = fn(*args)
    with _phase("bind"):
        ws, alphas = jax.vmap(lambda w, a: form.dist_finalize(w, a, d, n))(
            ws, alphas)
    return BatchedSolveResult(ws, alphas, jnp.ones((batch.tenants,), bool))


# --------------------------------------------------------------------------
# Solver registry, keyed on (formulation, backend)
# --------------------------------------------------------------------------

BACKENDS = ("local", "sharded", "pipelined")
_REGISTRY: dict[tuple[str, str], Callable] = {}


def register_solver(formulation: str, backend: str, fn: Callable) -> Callable:
    """Register a solver entry point under ``(formulation, backend)``.  The
    four ridge entries are registered by ``repro.core.bcd`` / ``.bdcd`` /
    ``.distributed`` at import; new formulations add theirs next to their
    Formulation class.  ``pipelined`` entries share the sharded signature
    (mesh leading) and differ only in the wire schedule
    (``SolverPlan.wire == "ring"``, DESIGN.md section 9)."""
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; expected one of {BACKENDS}")
    _REGISTRY[(formulation, backend)] = fn
    return fn


def get_solver(formulation: str, backend: str = "local") -> Callable:
    """Look up a solver.  ``local`` entries have the classical CA signature
    ``(X, y, lam, b, s, iters, key, **kw)``; ``sharded`` and ``pipelined``
    entries lead with the mesh: ``(mesh, X, y, lam, b, s, iters, key, **kw)``."""
    if (formulation, backend) not in _REGISTRY:
        # The built-in entries are registered by the sibling wrapper modules
        # at import; pull them in lazily so `from repro.core.engine import
        # get_solver` works without the package __init__ having run first.
        from . import accelerated, bcd, bdcd, distributed, proximal  # noqa: F401
    try:
        return _REGISTRY[(formulation, backend)]
    except KeyError:
        raise KeyError(
            f"no solver registered for ({formulation!r}, {backend!r}); "
            f"available: {sorted(_REGISTRY)}") from None


def registered_solvers() -> dict[tuple[str, str], Callable]:
    return dict(_REGISTRY)
