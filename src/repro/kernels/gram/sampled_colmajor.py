"""Column-tile sampled Pallas kernels: the dual layout without the pre-transpose.

The dual methods (Algorithms 3/4) sample *columns* of X.  An operand built
once (``ColMajorOperand``) stores X^T as row slabs and runs the row-sampled
kernels of ``sampled_kernel.py``.  A shard bound inside the sharded or
pipelined solve body on every call cannot be transposed there (the
``operand_transpose_free`` contract), so its operand (``_ColGatherOperand``)
keeps the ORIGINAL (dl, n) layout, and the kernels here gather its column
tiles:

* ``gram_packet_sampled_cols_pallas``: the fused dual packet
  ``(G = scale * Y^T Y + reg*I, r = scale_r * Y^T u)`` for ``Y = X[:, flat]``
  -- same output contract as the row-sampled packet on ``X.T``, zero extra
  resident copy.
* ``panel_apply_cols_pallas``: the deferred dual update
  ``out(d) = scale * X[:, flat] @ v`` (Eq. 15/19's ``w -= Y das / (lam n)``).
* ``panel_matvec_cols_pallas``: the standalone residual direction
  ``out(m) = scale * X[:, flat]^T t`` -- the batched multi-tenant engine's
  per-tenant residual, accumulated tile-for-tile like the fused packet's
  ``r`` cells so a shared-Gram batched solve reproduces the single-solve
  residual bitwise (DESIGN.md section 8).

Gather strategy (lane-aligned column DMA): a raw column copy would move bk
words with stride n -- 4-byte bursts the TPU DMA engines serialize, and a
slice the (8, 128) HBM tiling refuses.  Instead each sampled column
``c = flat[a]`` is fetched as the lane-aligned slab
``X[k*bk:(k+1)*bk, (c//LANE)*LANE : +LANE]`` and the target column
``c % LANE`` is selected out of the slab in VMEM (one-hot mask + lane-sum,
no arithmetic on the values, so the extracted panel is bitwise the gathered
column).  The slab fetch over-reads by the lane width: LANE x the useful
column bytes, the per-iteration traffic this layout pays for reading the
shard with no transpose (``cost_model.packet_hbm_bytes(layout="cols")`` carries
the term; sampled columns sharing a lane group are NOT deduplicated -- the
model is the worst case).

Slabs are fetched ``GATHER`` columns at a time into a (GATHER, bk, LANE)
scratch, so VMEM does not grow with the G tile: at (bm=128, bk=512, f32) the
two slab buffers take 2 * (8*512*128)*4B = 4 MiB and the two extracted
(bm, bk) panels 0.5 MiB, inside the 16 MiB scoped default.

Grid/tiling mirrors ``sampled_kernel.py`` with the contraction running over
X's ROWS (d): grid = (m/bm, m/bm, d/bk) with k innermost, symmetric skip +
mirror, reg fused on the last k step.  The extracted panels are (bm, bk) --
sampled column a as row a, restricted to the k-th row tile of X -- so the
MXU contractions are the row kernel's, verbatim.  Vectors travel as 2-D
``(1, k)`` blocks, as in the row kernels.

Requires m % bm == 0, bm % GATHER == 0, d % bk == 0, n % LANE == 0 (the
operand layer pads once; padded index slots point at column 0 and only touch
G/r rows >= m, padded d rows of X are zero so they contribute nothing).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .gram_kernel import (_acc_dtype, _add_diag_reg, _dot, _out, _vec_dot,
                          mirror_lower)

LANE = 128    # lane width of the aligned slab copies
GATHER = 8    # sampled columns whose slabs are in flight at once


def _gather_cols(idx_ref, x_ref, panel, slabs, sems, base, k,
                 bm: int, bk: int):
    """Fetch columns ``X[k*bk:(k+1)*bk, idx_ref[base+a]] -> panel[a]`` for
    a < bm via lane-aligned slab DMAs, GATHER at a time: start a chunk's
    slab copies on per-slot semaphores, then drain each and select its
    target lane into the panel."""

    def _chunk(c, _):
        off = base + c * GATHER

        def _copy(a):
            group = pl.multiple_of((idx_ref[off + a] // LANE) * LANE, LANE)
            return pltpu.make_async_copy(
                x_ref.at[pl.ds(k * bk, bk), pl.ds(group, LANE)],
                slabs.at[a], sems.at[a])

        def _start(a, _):
            _copy(a).start()
            return 0

        def _extract(a, _):
            _copy(a).wait()
            col = idx_ref[off + a] % LANE
            slab = slabs[a]                                  # (bk, LANE)
            lanes = jax.lax.broadcasted_iota(jnp.int32, (bk, LANE), 1)
            # One-hot select: x + 0 is exact, so panel row a IS column `col`.
            sel = jnp.sum(jnp.where(lanes == col, slab, jnp.zeros_like(slab)),
                          axis=1)
            panel[pl.ds(c * GATHER + a, 1), :] = sel[None, :]
            return 0

        jax.lax.fori_loop(0, GATHER, _start, 0)
        jax.lax.fori_loop(0, GATHER, _extract, 0)
        return 0

    jax.lax.fori_loop(0, bm // GATHER, _chunk, 0)


def _sampled_cols_packet_kernel(idx_ref, x_ref, u_ref, g_ref, r_ref, yi, yj,
                                slab_i, slab_j, sem_i, sem_j, *, scale: float,
                                reg: float, scale_r: float, n_k: int, bm: int,
                                bk: int, symmetric_skip: bool):
    i, j, k = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    acc = g_ref.dtype

    @pl.when(k == 0)
    def _init():
        g_ref[...] = jnp.zeros_like(g_ref)

    @pl.when(jnp.logical_and(k == 0, j == 0))
    def _init_r():
        r_ref[...] = jnp.zeros_like(r_ref)

    compute = jnp.logical_or(j <= i, jnp.logical_not(symmetric_skip))

    @pl.when(compute)
    def _gather_i():
        _gather_cols(idx_ref, x_ref, yi, slab_i, sem_i, i * bm, k, bm, bk)

    @pl.when(jnp.logical_and(compute, i != j))
    def _gather_j():
        _gather_cols(idx_ref, x_ref, yj, slab_j, sem_j, j * bm, k, bm, bk)

    @pl.when(compute)
    def _accumulate():
        a_i = yi[...]
        a_j = jnp.where(i == j, a_i, yj[...])
        g_ref[...] += scale * _dot(a_i, a_j, (1, 1), acc)

    # r = scale_r * Y^T u rides on the j == 0 cells (u tiled over d).
    @pl.when(j == 0)
    def _residual():
        r_ref[...] += scale_r * _vec_dot(u_ref[...], yi[...], 1, acc)

    @pl.when(jnp.logical_and(k == n_k - 1, i == j))
    def _reg():
        _add_diag_reg(g_ref, reg)


def _check_cols(X, m: int, bm: int, bk: int) -> None:
    d, n = X.shape
    if m % bm or bm % GATHER or d % bk or n % LANE:
        raise ValueError(
            f"flat ({m},) / X {X.shape} not tiled by bm={bm}, bk={bk}, "
            f"GATHER={GATHER}, LANE={LANE}")


def _scratch(bm: int, bk: int, dtype, panels: int):
    return ([pltpu.VMEM((bm, bk), dtype)] * panels            # panels
            + [pltpu.VMEM((GATHER, bk, LANE), dtype)] * panels  # slabs
            + [pltpu.SemaphoreType.DMA((GATHER,))] * panels)


@functools.partial(jax.jit, static_argnames=("scale", "reg", "scale_r", "bm",
                                             "bk", "symmetric_skip",
                                             "interpret"))
def gram_packet_sampled_cols_pallas(X: jax.Array, flat: jax.Array,
                                    u: jax.Array, *, scale: float = 1.0,
                                    reg: float = 0.0,
                                    scale_r: float | None = None,
                                    bm: int = 128, bk: int = 512,
                                    symmetric_skip: bool = True,
                                    interpret: bool = False
                                    ) -> tuple[jax.Array, jax.Array]:
    """(G, r) = (scale * Y^T Y + reg*I, scale_r * Y^T u) for Y = X[:, flat],
    gathered from the original (d, n) layout.  X (d, n) with d % bk == 0 and
    n % LANE == 0, flat (m,) int32 with m % bm == 0, u (d,)."""
    m = flat.shape[0]
    _check_cols(X, m, bm, bk)
    n_k = X.shape[0] // bk
    acc = _acc_dtype(X.dtype)

    kernel = functools.partial(
        _sampled_cols_packet_kernel, scale=scale, reg=reg,
        scale_r=(scale if scale_r is None else scale_r), n_k=n_k, bm=bm,
        bk=bk, symmetric_skip=symmetric_skip)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                       # flat -> SMEM, pre-grid
        grid=(m // bm, m // bm, n_k),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),   # X in HBM
            pl.BlockSpec((1, bk), lambda i, j, k, idx: (0, k)),  # u tile (d)
        ],
        out_specs=[
            pl.BlockSpec((bm, bm), lambda i, j, k, idx: (i, j)),  # G tile
            pl.BlockSpec((1, bm), lambda i, j, k, idx: (0, i)),   # r tile
        ],
        scratch_shapes=_scratch(bm, bk, X.dtype, panels=2),
    )
    g, r = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[_out((m, m), acc, X, flat, u),
                   _out((1, m), acc, X, flat, u)],
        interpret=interpret,
    )(flat, X, u.reshape(1, -1))

    if symmetric_skip:
        g = mirror_lower(g, bm)
    return g, r[0]


def _panel_matvec_cols_kernel(idx_ref, x_ref, t_ref, o_ref, ybuf, slabs, sems,
                              *, scale: float, bm: int, bk: int):
    i, k = pl.program_id(0), pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    _gather_cols(idx_ref, x_ref, ybuf, slabs, sems, i * bm, k, bm, bk)
    # Same contraction cell as the fused packet's residual (j == 0 cells of
    # _sampled_cols_packet_kernel), accumulated in the same k order, so this
    # standalone matvec is bitwise the fused r when tiles match.
    o_ref[...] += scale * _vec_dot(t_ref[...], ybuf[...], 1, o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "bm", "bk", "interpret"))
def panel_matvec_cols_pallas(X: jax.Array, flat: jax.Array, t: jax.Array, *,
                             scale: float = 1.0, bm: int = 128, bk: int = 512,
                             interpret: bool = False) -> jax.Array:
    """out(m) = scale * X[:, flat]^T t from the original (d, n) layout -- the
    dual residual direction as a standalone kernel.  Grid (m/bm, d/bk) with
    the contraction (k over d) innermost so each output tile accumulates in
    VMEM exactly like the fused packet's r tiles."""
    m = flat.shape[0]
    _check_cols(X, m, bm, bk)
    acc = _acc_dtype(X.dtype)

    kernel = functools.partial(_panel_matvec_cols_kernel, scale=scale, bm=bm,
                               bk=bk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(m // bm, X.shape[0] // bk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),  # X in HBM
            pl.BlockSpec((1, bk), lambda i, k, idx: (0, k)),   # t tile (d)
        ],
        out_specs=pl.BlockSpec((1, bm), lambda i, k, idx: (0, i)),
        scratch_shapes=_scratch(bm, bk, X.dtype, panels=1),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=_out((1, m), acc, X, flat, t),
        interpret=interpret,
    )(flat, X, t.reshape(1, -1))
    return out[0]


def _panel_apply_cols_kernel(idx_ref, x_ref, v_ref, o_ref, ybuf, slabs, sems,
                             *, scale: float, bm: int, bk: int):
    k, t = pl.program_id(0), pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    _gather_cols(idx_ref, x_ref, ybuf, slabs, sems, t * bm, k, bm, bk)
    o_ref[...] += scale * _vec_dot(v_ref[...], ybuf[...], 0, o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "bm", "bk", "interpret"))
def panel_apply_cols_pallas(X: jax.Array, flat: jax.Array, v: jax.Array, *,
                            scale: float = 1.0, bm: int = 128, bk: int = 512,
                            interpret: bool = False) -> jax.Array:
    """out(d) = scale * X[:, flat] @ v from the original layout -- the dual's
    deferred ``w -= Y das / (lam n)`` without a pre-transposed operand.  Grid
    (d/bk, m/bm) with the sampled-column tiles innermost so each output tile
    accumulates in VMEM; padded index slots must carry v == 0 (the operand
    layer guarantees this)."""
    m = flat.shape[0]
    _check_cols(X, m, bm, bk)
    d = X.shape[0]
    acc = _acc_dtype(X.dtype)

    kernel = functools.partial(_panel_apply_cols_kernel, scale=scale, bm=bm,
                               bk=bk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(d // bk, m // bm),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),  # X in HBM
            pl.BlockSpec((1, bm), lambda k, t, idx: (0, t)),   # v tile
        ],
        out_specs=pl.BlockSpec((1, bk), lambda k, t, idx: (0, k)),
        scratch_shapes=_scratch(bm, bk, X.dtype, panels=1),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=_out((1, d), acc, X, flat, v),
        interpret=interpret,
    )(flat, X, v.reshape(1, -1))
    return out[0]
