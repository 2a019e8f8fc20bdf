"""The PacketOperand layer: what the packet kernels gather, made first-class.

An operand object owns the data array in the layout its kernels read, and
its GATHER STRATEGY:

* :class:`RowMajorOperand` -- logical array (S, C), samples are rows; the
  index-prefetched row-DMA kernels of ``sampled_kernel.py``.  Stored in the
  slab layout (S, C_pad/128, 128) those kernels DMA from.
* :class:`ColMajorOperand` -- logical array (C, S), samples are COLUMNS:
  the dual's build-once operand over X (d, n).  Stored as the row slabs of
  X^T, (S, C_pad/128, 128), transposed once when the operand is built, so
  it runs the same row-DMA kernels (one 4 KB DMA per sampled column block).
* ``_ColGatherOperand`` -- the same logical (C, S) array stored in its
  ORIGINAL orientation, zero-padded to (C_pad, S_pad), read by the
  lane-aligned column-tile kernels of ``sampled_colmajor.py``.  The sharded
  and pipelined duals bind their shard through it inside the ``shard_map``
  body on every call, where no operand transpose is allowed (the
  ``operand_transpose_free`` contract).
* :class:`MaterializedOperand` -- array (S, S) of ALREADY-FORMED products
  (a kernel matrix K): the "Gram" is a gather, not a contraction.  This is
  the kernel-BDCD prerequisite (arXiv:2406.18001); smoke-proven through the
  same dispatch.

Uniform semantics in terms of the implicit sampled panel ``Y(flat)``,
shape (m, C):

    packet(flat, u):  G = scale * Y Y^T + reg*I,   r = scale_r * Y u
    apply(flat, v):   out(C) = scale * Y^T v
    matvec(flat, t):  out(m) = scale * Y t

(for ``MaterializedOperand`` the panel is the implicit factor with
``Y Y^T = K[flat][:, flat]`` and ``Y u = K[flat, :] u`` -- the kernel trick.)
The gather operands also offer the same products over EVERY sample,
``full_apply(v)`` (``Y_all^T v``) and ``full_matvec(t)`` (``Y_all t``),
computed densely for warm starts and metrics; ``ColMajorOperand`` adds
``full_matmat(T)``, ``full_matvec`` of several vectors in one pass.

Padding and relayout happen ONCE, when the operand is built (a physical
copy of the dataset); the kernels then read the stored array as is.  Build
the operand once and pass it where X goes -- the formulations' ``bind``
accepts it in place of the raw array -- to keep a single resident copy.
The gather operands are pytrees, so they pass through ``jax.jit`` as
arguments.

Registration IS the protocol: a new operand kind implements these three
methods (plus ``dtype``/``layout``) and every consumer -- the engine's one
hot-loop body, ``ops.py``'s public entry points, the benchmarks -- dispatches
through it with zero edits.  ``as_operand`` keeps raw arrays working
everywhere (they mean row-major).

Knob resolution (``impl``/``bm``/``bk``) stays in ``ops.py``; the methods
here receive resolved knobs and own only padding + kernel selection.  Tile
defaults and the chip's tile rules live in ``tuning``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import ClassVar, Protocol, runtime_checkable

import jax
import jax.numpy as jnp

from . import ref, tuning
from .gram_kernel import _acc_dtype
from .sampled_colmajor import (LANE, gram_packet_sampled_cols_pallas,
                               panel_apply_cols_pallas,
                               panel_matvec_cols_pallas)
from .sampled_kernel import (gram_packet_sampled_pallas, panel_apply_pallas,
                             panel_matvec_pallas)

_HI = jax.lax.Precision.HIGHEST


def _pad_axis(x: jax.Array, mult: int, axis: int) -> jax.Array:
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _pad_to(x: jax.Array, size: int) -> jax.Array:
    """Zero-pad a vector to ``size``."""
    return jnp.pad(x, (0, size - x.shape[0])) if x.shape[0] < size else x


def _index_pad(flat, bm):
    return _pad_axis(flat.astype(jnp.int32), bm, 0)


# The one-time layout builds run as single compiled programs, so the only
# full-size copy alive next to the input is the output.
_SLAB_ROWS = 512     # rows relaid per step of the slab build


@functools.partial(jax.jit, static_argnums=1)
def _row_slabs(x, c_pad: int):
    """(S, C) -> (S, c_pad/LANE, LANE) slabs, zero-padded.  Built a block of
    rows at a time into the output: relaying the whole array at once makes
    XLA hold two full-size intermediates (17 GB at real-sim's 6 GB).  The
    last block is clamped to end at row S, so it rewrites rows the previous
    block already wrote, with the same values."""
    S = x.shape[0]
    rows = min(_SLAB_ROWS, S)

    def block(i, out):
        start = jnp.minimum(i * rows, S - rows)
        blk = jax.lax.dynamic_slice_in_dim(x, start, rows, 0)
        blk = _pad_axis(blk, c_pad, 1).reshape(rows, c_pad // LANE, LANE)
        return jax.lax.dynamic_update_slice_in_dim(out, blk, start, 0)

    # zeros_like keeps x's varying mesh axes (inside shard_map).
    out = jnp.zeros_like(x, shape=(S, c_pad // LANE, LANE))
    return jax.lax.fori_loop(0, -(-S // rows), block, out)


_SLAB_COLS = 128     # columns of x transposed per step of the X^T slab build


@functools.partial(jax.jit, static_argnums=1)
def _col_slabs(x, c_pad: int):
    """(C, S) -> the slabs of x^T, (S, c_pad/LANE, LANE), zero-padded: the
    transposing sibling of :func:`_row_slabs`.  Each step slices a block of
    x's columns, transposes and pads only that block (32 MB at news20's
    d = 62,061), so no transposed copy of x exists beside the output.  The
    last block is clamped to end at column S, as in ``_row_slabs``."""
    S = x.shape[1]
    cols = min(_SLAB_COLS, S)

    def block(i, out):
        start = jnp.minimum(i * cols, S - cols)
        blk = jax.lax.dynamic_slice_in_dim(x, start, cols, 1).T
        blk = _pad_axis(blk, c_pad, 1).reshape(cols, c_pad // LANE, LANE)
        return jax.lax.dynamic_update_slice_in_dim(out, blk, start, 0)

    out = jnp.zeros_like(x, shape=(S, c_pad // LANE, LANE))
    return jax.lax.fori_loop(0, -(-S // cols), block, out)


@functools.partial(jax.jit, static_argnums=1)
def _col_padded(x, c_pad: int):
    C, S = x.shape
    return jnp.pad(x, ((0, c_pad - C), (0, (-S) % LANE)))


@runtime_checkable
class PacketOperand(Protocol):
    """A packet operand: the array, its layout, and its gather strategy."""
    array: jax.Array
    layout: ClassVar[str]

    @property
    def dtype(self): ...
    @property
    def samples(self) -> int: ...
    @property
    def contraction(self) -> int: ...
    def packet(self, flat, u, *, scale, reg, scale_r, impl, bm, bk,
               symmetric_skip): ...
    def apply(self, flat, v, *, scale, impl, bm, bk): ...
    def matvec(self, flat, t, *, scale, impl, bm, bk): ...


class _GatherOperand:
    """Shared storage of the gather operands: the kernel-layout
    ``array`` plus the logical ``(samples, contraction)`` sizes.  A pytree
    whose only leaf is the array."""
    layout: ClassVar[str]

    @classmethod
    def of(cls, x):
        """``x`` itself if it is already this kind of operand, else the
        operand built from the raw array ``x``."""
        return x if isinstance(x, cls) else cls(x)

    def _set(self, array, samples: int, contraction: int):
        self.array = array
        self._sizes = (samples, contraction)

    @property
    def dtype(self):
        return self.array.dtype

    @property
    def samples(self) -> int:
        return self._sizes[0]

    @property
    def contraction(self) -> int:
        return self._sizes[1]

    @property
    def c_pad(self) -> int:
        return tuning.padded_contraction(self.contraction, self.dtype,
                                         self.layout)

    def _tiles(self, m, bm, bk):
        return tuning.resolve_tiles(m, self.contraction, self.dtype, bm, bk,
                                    self.layout)

    def tree_flatten(self):
        return (self.array,), self._sizes

    @classmethod
    def tree_unflatten(cls, sizes, leaves):
        obj = object.__new__(cls)
        obj._set(leaves[0], *sizes)
        return obj


class _SlabOperand(_GatherOperand):
    """The row-DMA kernels' operand (``sampled_kernel.py``): the sample-major
    array A (S, C) -- the sampled panel is Y = A[flat, :] -- stored as slabs
    (S, C_pad/LANE, LANE): C zero-padded to the row layout's contraction
    tile, then split into 128-lane rows.  Subclasses choose how A relates to
    the array they are built from."""
    layout: ClassVar[str] = "rows"

    def _panel(self, flat):
        """The sampled rows (m, C_pad), zero-padded columns (ref path)."""
        return self.array[flat].reshape(flat.shape[0], self.c_pad)

    def packet(self, flat, u, *, scale, reg, scale_r, impl, bm, bk,
               symmetric_skip):
        up = _pad_to(u, self.c_pad)
        if impl == "ref":
            return ref.gram_packet_ref(self._panel(flat), up, scale, reg,
                                       scale_r)
        m = flat.shape[0]
        bm_eff, bk_eff = self._tiles(m, bm, bk)
        G, r = gram_packet_sampled_pallas(
            self.array, _index_pad(flat, bm_eff), up, scale=scale, reg=reg,
            scale_r=scale_r, bm=bm_eff, bk=bk_eff,
            symmetric_skip=symmetric_skip,
            interpret=(impl == "pallas_interpret"))
        return G[:m, :m], r[:m]

    def apply(self, flat, v, *, scale, impl, bm, bk):
        n = self.contraction
        if impl == "ref":
            acc = _acc_dtype(self.dtype)
            out = scale * jnp.einsum("mk,m->k", self._panel(flat), v,
                                     precision=_HI, preferred_element_type=acc)
            return out.astype(acc)[:n]
        bm_eff, bk_eff = self._tiles(flat.shape[0], bm, bk)
        out = panel_apply_pallas(self.array, _index_pad(flat, bm_eff),
                                 _pad_axis(v, bm_eff, 0), scale=scale,
                                 bm=bm_eff, bk=bk_eff,
                                 interpret=(impl == "pallas_interpret"))
        return out[:n]

    def matvec(self, flat, t, *, scale, impl, bm, bk):
        tp = _pad_to(t, self.c_pad)
        if impl == "ref":
            acc = _acc_dtype(self.dtype)
            out = scale * jnp.einsum("mk,k->m", self._panel(flat), tp,
                                     precision=_HI, preferred_element_type=acc)
            return out.astype(acc)
        m = flat.shape[0]
        bm_eff, bk_eff = self._tiles(m, bm, bk)
        out = panel_matvec_pallas(self.array, _index_pad(flat, bm_eff), tp,
                                  scale=scale, bm=bm_eff, bk=bk_eff,
                                  interpret=(impl == "pallas_interpret"))
        return out[:m]

    def full_apply(self, v):
        """A^T v over every sample: out(C)."""
        out = jnp.einsum("s,sgl->gl", v, self.array, precision=_HI,
                         preferred_element_type=_acc_dtype(self.dtype))
        return out.reshape(-1)[:self.contraction].astype(self.dtype)

    def full_matvec(self, t):
        """A t over every sample: out(S)."""
        tp = _pad_to(t, self.c_pad).reshape(-1, LANE)
        out = jnp.einsum("sgl,gl->s", self.array, tp, precision=_HI,
                         preferred_element_type=_acc_dtype(self.dtype))
        return out.astype(self.dtype)


@jax.tree_util.register_pytree_node_class
class RowMajorOperand(_SlabOperand):
    """Logical array X (S, C); samples rows: Y = X[flat, :].  The slabs are
    X's rows; the relayout is one copy, made here."""

    def __init__(self, x):
        S, C = x.shape
        c_pad = tuning.padded_contraction(C, x.dtype, self.layout)
        self._set(_row_slabs(x, c_pad), S, C)

    @property
    def shape(self) -> tuple[int, int]:
        return (self.samples, self.contraction)


@jax.tree_util.register_pytree_node_class
class ColMajorOperand(_SlabOperand):
    """Logical array X (C, S); samples COLUMNS: Y = X[:, flat].T.  The
    dual's build-once operand: stored as the row slabs of X^T, so its
    packets, applies and matvecs run the row-DMA kernels.  The transpose is
    the one copy, made here block by block (:func:`_col_slabs`); the result
    equals ``RowMajorOperand(X.T).array`` bit for bit."""

    def __init__(self, x):
        C, S = x.shape
        c_pad = tuning.padded_contraction(C, x.dtype, self.layout)
        self._set(_col_slabs(x, c_pad), S, C)

    @property
    def shape(self) -> tuple[int, int]:
        return (self.contraction, self.samples)

    def full_matmat(self, T):
        """X^T t for every row t of T (k, C), in one pass over the slabs:
        out(k, S)."""
        Tp = jnp.pad(T, ((0, 0), (0, self.c_pad - T.shape[1])))
        out = jnp.einsum("kgl,sgl->ks", Tp.reshape(T.shape[0], -1, LANE),
                         self.array, precision=_HI,
                         preferred_element_type=_acc_dtype(self.dtype))
        return out.astype(self.dtype)


@jax.tree_util.register_pytree_node_class
class _ColGatherOperand(_GatherOperand):
    """Logical array X (C, S); samples COLUMNS of the original layout:
    Y = X[:, flat].T, through the lane-aligned column-tile gather kernels
    (``sampled_colmajor.py``) -- the shard-local dual operand, built inside
    the sharded solve's body on every call, where no transpose of the
    operand is allowed.  Stored zero-padded to (C_pad, S_pad): C to the
    layout's contraction tile (the kernels' row tiles) and S to the 128-lane
    width (every slab copy in bounds).  Padded index slots clamp to column 0
    and only touch G/r rows >= m."""
    layout: ClassVar[str] = "cols"

    def __init__(self, x):
        C, S = x.shape
        c_pad = tuning.padded_contraction(C, x.dtype, self.layout)
        self._set(_col_padded(x, c_pad), S, C)

    @property
    def shape(self) -> tuple[int, int]:
        return (self.contraction, self.samples)

    def packet(self, flat, u, *, scale, reg, scale_r, impl, bm, bk,
               symmetric_skip):
        up = _pad_to(u, self.c_pad)
        if impl == "ref":
            return ref.gram_packet_sampled_cols_ref(self.array, flat, up,
                                                    scale, reg, scale_r)
        m = flat.shape[0]
        bm_eff, bk_eff = self._tiles(m, bm, bk)
        G, r = gram_packet_sampled_cols_pallas(
            self.array, _index_pad(flat, bm_eff), up, scale=scale, reg=reg,
            scale_r=scale_r, bm=bm_eff, bk=bk_eff,
            symmetric_skip=symmetric_skip,
            interpret=(impl == "pallas_interpret"))
        return G[:m, :m], r[:m]

    def apply(self, flat, v, *, scale, impl, bm, bk):
        d = self.contraction
        if impl == "ref":
            return ref.panel_apply_cols_ref(self.array, flat, v, scale)[:d]
        bm_eff, bk_eff = self._tiles(flat.shape[0], bm, bk)
        out = panel_apply_cols_pallas(self.array, _index_pad(flat, bm_eff),
                                      _pad_axis(v, bm_eff, 0), scale=scale,
                                      bm=bm_eff, bk=bk_eff,
                                      interpret=(impl == "pallas_interpret"))
        return out[:d]

    def matvec(self, flat, t, *, scale, impl, bm, bk):
        # out(m) = scale * array[:, flat]^T t.  The batched multi-tenant
        # engine's per-tenant dual residual: each route mirrors the fused
        # packet's r (same expression on ref, same accumulation cells in the
        # kernel) so batched residuals match single-solve residuals bitwise.
        tp = _pad_to(t, self.c_pad)
        if impl == "ref":
            return ref.panel_matvec_cols_ref(self.array, flat, tp, scale)
        m = flat.shape[0]
        bm_eff, bk_eff = self._tiles(m, bm, bk)
        out = panel_matvec_cols_pallas(self.array, _index_pad(flat, bm_eff),
                                       tp, scale=scale, bm=bm_eff, bk=bk_eff,
                                       interpret=(impl == "pallas_interpret"))
        return out[:m]

    def full_apply(self, v):
        """X v over every sampled column: out(C)."""
        vp = _pad_to(v, self.array.shape[1])
        out = jnp.matmul(self.array, vp, precision=_HI,
                         preferred_element_type=_acc_dtype(self.dtype))
        return out[:self.contraction].astype(self.dtype)

    def full_matvec(self, t):
        """X^T t over every sampled column: out(S)."""
        tp = _pad_to(t, self.c_pad)
        out = jnp.matmul(tp, self.array, precision=_HI,
                         preferred_element_type=_acc_dtype(self.dtype))
        return out[:self.samples].astype(self.dtype)


@dataclasses.dataclass(frozen=True)
class MaterializedOperand:
    """Array K (S, S) of pre-materialized products (a kernel matrix): the
    packet's Gram is GATHERED, not contracted -- G = scale * K[flat][:, flat]
    + reg*I, r = scale_r * K[flat, :] u.  There is no panel to fuse away, so
    every impl runs the same jnp gather (validated like any other impl
    string; the kernel-BDCD formulation of arXiv:2406.18001 binds through
    here with zero engine edits)."""
    array: jax.Array
    layout: ClassVar[str] = "materialized"

    @property
    def dtype(self):
        return self.array.dtype

    @property
    def samples(self) -> int:
        return self.array.shape[0]

    @property
    def contraction(self) -> int:
        return self.array.shape[1]

    def packet(self, flat, u, *, scale, reg, scale_r, impl, bm, bk,
               symmetric_skip):
        acc = _acc_dtype(self.dtype)
        K = self.array
        rows = K[flat, :].astype(acc)
        G = scale * rows[:, flat] + reg * jnp.eye(flat.shape[0], dtype=acc)
        sr = scale if scale_r is None else scale_r
        r = sr * (rows @ u.astype(acc))
        return G, r

    def apply(self, flat, v, *, scale, impl, bm, bk):
        acc = _acc_dtype(self.dtype)
        out = scale * jnp.einsum("mk,m->k", self.array[flat, :], v,
                                 preferred_element_type=acc)
        return out.astype(acc)

    def matvec(self, flat, t, *, scale, impl, bm, bk):
        acc = _acc_dtype(self.dtype)
        out = scale * jnp.einsum("mk,k->m", self.array[flat, :], t,
                                 preferred_element_type=acc)
        return out.astype(acc)


def as_operand(x) -> PacketOperand:
    """Normalize: PacketOperands pass through; raw arrays mean row-major."""
    if isinstance(x, PacketOperand):
        return x
    return RowMajorOperand(x)
