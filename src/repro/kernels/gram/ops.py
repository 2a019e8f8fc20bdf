"""Public ops for the Gram packet: pad-to-tile, backend dispatch, unpad.

This is the Gram-backend dispatch layer: every Gram-shaped product in the
solvers goes through it (re-exported as ``repro.core.gram_packet`` etc.).  On
TPU it runs the Pallas kernels; everywhere else (this CPU container, and
inside the dry-run lowering) it uses the jnp reference, which XLA fuses well.
``impl`` can force either path; tests force ``impl="pallas_interpret"`` to
execute the kernel bodies on CPU and assert solver-level equivalence against
``impl="ref"``.

Entry points:

* ``gram_packet(A, u)`` -- fused (G, r) on a pre-materialized operand (kept
  for callers that already hold the panel, e.g. TSQR's stacked R factors).
* ``gram_packet_sampled(X, flat, u)`` -- the panel-free hot path: the same
  packet for the sampled panel ``Y`` without materializing Y.  ``X`` is a
  :mod:`~repro.kernels.gram.operands` PacketOperand (row-major /
  column-major / pre-materialized -- the operand owns the gather strategy)
  or a raw array, which means row-major: ``Y = X[flat, :]``.  All solver
  formulations build their packets here through the operand their
  ``bind``/``bind_shard`` produced.
* ``panel_apply(X, flat, v)`` / ``panel_matvec(X, flat, t)`` -- the deferred
  vector updates (``alpha += Y^T dws``, ``w -= Y das``) and the sample-side
  matvec, also panel-free and also operand-dispatched.
* ``gram(A)`` -- Gram only, dispatched to a residual-free kernel (the packet
  kernel is never fed a zeros u).
* ``normal_matvec(X, v)`` -- the CG normal-equations operator
  ``scale * X X^T v + lam v`` as two streaming panel products.

Tile sizes: callers may pin ``bm``/``bk``; otherwise ``tuning.pick_tiles``
consults the autotuned (sb, n, dtype, layout) table populated by
``benchmarks/gram_autotune.py`` and falls back to the layout's default.
Either way the tiles obey the chip's block rules (``tuning.tile_problems``);
a pinned tile that breaks them raises.

Knob threading: callers that issue several packet calls with the same
backend/tile choices (the solver engine) carry ONE :class:`PacketPlan` and
pass it as ``plan=`` instead of re-threading ``impl``/``bm``/``bk`` through
every signature.  Explicitly-passed knobs win over the plan's, so a plan acts
as a bundle of defaults (DESIGN.md section 5.4).
"""
from __future__ import annotations

import dataclasses
import operator

import jax
import jax.numpy as jnp

from . import ref
from .gram_kernel import gram_packet_pallas, gram_pallas
from .operands import _pad_axis, as_operand
from .tuning import resolve_tiles

_IMPLS = ("ref", "pallas", "pallas_interpret")


@dataclasses.dataclass(frozen=True)
class PacketPlan:
    """One bundle of kernel-dispatch knobs for a sequence of packet calls.

    ``impl`` selects the backend (``None`` auto-selects per JAX backend);
    ``bm``/``bk`` pin the kernel tiles (``None`` consults the tuning table).
    The solver engine builds one plan per solve and hands it to every
    ``gram_packet_sampled`` / ``panel_apply`` call in the hot loop, replacing
    the per-call ``impl=``/``tiles=`` threading of PRs 1-2.

    Knobs are validated here, at construction: a typo'd ``impl`` or a
    zero/negative tile fails fast with the accepted set instead of erroring
    at the first kernel call inside a jitted scan.
    """
    impl: str | None = None
    bm: int | None = None
    bk: int | None = None

    def __post_init__(self):
        if self.impl is not None:
            _check_impl(self.impl)
        for name in ("bm", "bk"):
            _check_tile(name, getattr(self, name))

    @classmethod
    def make(cls, impl: str | None = None,
             tiles: tuple[int, int] | None = None) -> "PacketPlan":
        """Build from the public solver knobs (``impl``, ``tiles=(bm, bk)``)."""
        if tiles is None:
            return cls(impl=impl)
        if len(tiles) != 2:
            raise ValueError(f"tiles={tiles!r} must be a (bm, bk) pair")
        return cls(impl=impl, bm=tiles[0], bk=tiles[1])


def _check_positive_int(name: str, v) -> None:
    """Shared fail-fast knob check (PacketPlan tiles, SolverPlan b/s/unroll):
    ints and numpy integers >= 1; bools and floats rejected."""
    try:
        iv = operator.index(v)
    except TypeError:
        iv = None
    if isinstance(v, bool) or iv is None or iv < 1:
        raise ValueError(f"{name}={v!r} must be a positive int")


def _check_tile(name: str, v) -> None:
    """Tiles are positive ints or None (= consult the tuning table); 0 is an
    error, not "unset" -- it used to falsy-fall-through to the plan's tiles."""
    if v is not None:
        _check_positive_int(f"kernel tile {name}", v)


def _with_plan(plan: PacketPlan | None, impl, bm, bk):
    """Resolve per-call knobs against the plan: explicitly-passed arguments
    win; only ``None`` means "defer to the plan" (``bm=0`` raises rather than
    silently resolving to the plan's tile)."""
    _check_tile("bm", bm)
    _check_tile("bk", bk)
    if plan is None:
        return impl, bm, bk
    return (impl if impl is not None else plan.impl,
            bm if bm is not None else plan.bm,
            bk if bk is not None else plan.bk)


def _auto_impl() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def _check_impl(impl: str) -> None:
    # Called before the ref/kernel branch in every entry point (and at
    # PacketPlan construction), so the listed set is the true accepted set.
    if impl not in _IMPLS:
        raise ValueError(
            f"unknown gram impl {impl!r}; expected one of {_IMPLS}")


def _resolve(plan, impl, bm, bk) -> tuple[str, int | None, int | None]:
    impl, bm, bk = _with_plan(plan, impl, bm, bk)
    impl = impl or _auto_impl()
    _check_impl(impl)
    return impl, bm, bk


def _tiles(m: int, n: int, dtype, bm: int | None, bk: int | None
           ) -> tuple[int, int]:
    """(bm, bk) for a materialized operand: the shared tile rule at
    layout="dense" (the operand is padded per call to these tiles)."""
    return resolve_tiles(m, n, dtype, bm, bk, "dense")


def gram_packet(A: jax.Array, u: jax.Array, *, scale: float = 1.0,
                reg: float = 0.0, scale_r: float | None = None,
                impl: str | None = None,
                bm: int | None = None, bk: int | None = None,
                symmetric_skip: bool = True,
                plan: PacketPlan | None = None) -> tuple[jax.Array, jax.Array]:
    """Fused (G, r) = (scale*A@A^T + reg*I, scale_r*A@u); A (m, n), u (n,).

    ``scale_r`` defaults to ``scale``.  ``impl`` is one of ``"ref"`` (jnp,
    XLA-fused), ``"pallas"`` (TPU kernel), ``"pallas_interpret"`` (kernel body
    executed on CPU, the test path); ``None`` auto-selects per backend.
    ``bm``/``bk`` default to the tuning-table pick for (m, n, dtype).

    Zero padding is exact: padded k-columns contribute 0 to both products and
    padded m-rows are sliced off (their diagonal reg never leaves the pad).
    """
    impl, bm, bk = _resolve(plan, impl, bm, bk)
    if impl == "ref":
        return ref.gram_packet_ref(A, u, scale, reg, scale_r)
    m, n = A.shape
    bm_eff, bk_eff = _tiles(m, n, A.dtype, bm, bk)
    Ap = _pad_axis(_pad_axis(A, bm_eff, 0), bk_eff, 1)
    up = _pad_axis(u, bk_eff, 0)
    G, r = gram_packet_pallas(
        Ap, up, scale=scale, reg=reg, scale_r=scale_r, bm=bm_eff, bk=bk_eff,
        symmetric_skip=symmetric_skip,
        interpret=(impl == "pallas_interpret"))
    return G[:m, :m], r[:m]


def gram_packet_sampled(X, flat: jax.Array, u: jax.Array, *,
                        scale: float = 1.0, reg: float = 0.0,
                        scale_r: float | None = None, impl: str | None = None,
                        bm: int | None = None, bk: int | None = None,
                        symmetric_skip: bool = True,
                        plan: PacketPlan | None = None
                        ) -> tuple[jax.Array, jax.Array]:
    """Panel-free packet: (G, r) = (scale*Y Y^T + reg*I, scale_r*Y u) for the
    operand's sampled panel Y *without materializing Y*.  ``X`` is a
    PacketOperand or a raw (d, n) array (row-major: ``Y = X[flat, :]``);
    ``flat`` (m,) int indices (duplicates allowed), ``u`` of the operand's
    contraction length.

    The operand owns the gather: the row-slab operands (row-major, and the
    column-major one stored as the slabs of X^T) scalar-prefetch ``flat``
    and stream sampled rows HBM->VMEM inside the kernel; the shard-local
    column gather fetches lane-aligned column tiles of the original layout;
    materialized operands gather the already-formed products.  Padding is exact in every layout
    (padded contraction entries are zero and padded index slots only touch
    G/r rows >= m, which are sliced off before the regularized diagonal can
    leak).
    """
    impl, bm, bk = _resolve(plan, impl, bm, bk)
    return as_operand(X).packet(flat, u, scale=scale, reg=reg,
                                scale_r=scale_r, impl=impl, bm=bm, bk=bk,
                                symmetric_skip=symmetric_skip)


def panel_apply(X, flat: jax.Array, v: jax.Array, *,
                scale: float = 1.0, impl: str | None = None,
                bm: int | None = None, bk: int | None = None,
                plan: PacketPlan | None = None) -> jax.Array:
    """out = scale * Y^T v for the operand's sampled panel, panel-free: the
    deferred vector updates (``alpha += Y^T dws`` primal, ``w -= Y das``
    dual).  Output length is the operand's contraction dimension.  Padded
    index slots carry v == 0, so their gathered panel rows contribute 0."""
    impl, bm, bk = _resolve(plan, impl, bm, bk)
    return as_operand(X).apply(flat, v, scale=scale, impl=impl, bm=bm, bk=bk)


def panel_matvec(X, flat: jax.Array, t: jax.Array, *,
                 scale: float = 1.0, impl: str | None = None,
                 bm: int | None = None, bk: int | None = None,
                 plan: PacketPlan | None = None) -> jax.Array:
    """out(m) = scale * Y t, panel-free (the residual direction)."""
    impl, bm, bk = _resolve(plan, impl, bm, bk)
    return as_operand(X).matvec(flat, t, scale=scale, impl=impl, bm=bm, bk=bk)


def normal_matvec(X: jax.Array, v: jax.Array, *, lam: float = 0.0,
                  scale: float = 1.0, impl: str | None = None,
                  bm: int | None = None, bk: int | None = None,
                  plan: PacketPlan | None = None) -> jax.Array:
    """(scale * X X^T + lam I) v as two streaming panel products -- the CG
    normal-equations operator (``core/krylov.py``), never a d x d matrix.

    Unlike the sampled packets, ``impl=None`` stays on the ref path on every
    backend: this is a dense matvec, which XLA's native matmul already
    schedules well on TPU, and routing it through the identity-index row-DMA
    kernels by default would handicap the CG baseline the solvers are
    compared against.  The kernel route is opt-in via an explicit ``impl``.
    """
    impl, bm, bk = _with_plan(plan, impl, bm, bk)
    impl = impl or "ref"
    _check_impl(impl)
    if impl == "ref":
        return X @ (X.T @ v) * scale + lam * v
    d = X.shape[0]
    rows = jnp.arange(d, dtype=jnp.int32)
    t = panel_apply(X, rows, v, impl=impl, bm=bm, bk=bk)          # X^T v
    out = panel_matvec(X, rows, t.astype(X.dtype), scale=scale, impl=impl,
                       bm=bm, bk=bk)                              # X (X^T v)
    return out + lam * v


def gram(A: jax.Array, *, scale: float = 1.0, reg: float = 0.0,
         impl: str | None = None, bm: int | None = None,
         bk: int | None = None, symmetric_skip: bool = True,
         plan: PacketPlan | None = None) -> jax.Array:
    """G = scale * A @ A^T + reg * I, via the residual-free Gram kernel (the
    packet kernel's u path is never fed, computed, or written)."""
    impl, bm, bk = _resolve(plan, impl, bm, bk)
    if impl == "ref":
        return ref.gram_ref(A, scale, reg)
    m, n = A.shape
    bm_eff, bk_eff = _tiles(m, n, A.dtype, bm, bk)
    Ap = _pad_axis(_pad_axis(A, bm_eff, 0), bk_eff, 1)
    G = gram_pallas(Ap, scale=scale, reg=reg, bm=bm_eff, bk=bk_eff,
                    symmetric_skip=symmetric_skip,
                    interpret=(impl == "pallas_interpret"))
    return G[:m, :m]
