"""Compile the hot-path Pallas kernels for a TPU v5e at the paper's full
widths, without a chip: the compiler that refuses a misaligned block, an
illegal tile or an oversized VMEM scratch runs here, on shapes only.

Shapes: the real-sim primal operand (20,958 x 72,309, the row gather), the
news20 dual operand (62,061 x 15,935) as the row slabs of X^T (the row
gather) and in its original layout (the column gather, which the sharded
dual runs on its shard), f32, with sb = 256 sampled indices (b = 16,
s = 16).  Every compile must emit the
kernel (``tpu_custom_call``).  The topology is described inside a fixture,
so only the worker that runs this file loads the TPU compiler.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import SolverPlan, s_step_solve
from repro.kernels.gram import (ColMajorOperand, RowMajorOperand, gram_packet,
                                tuning)
from repro.kernels.gram.operands import _col_slabs, _ColGatherOperand

REAL_SIM = (20958, 72309)     # (d, n): primal, samples rows
NEWS20 = (62061, 15935)       # (d, n): dual, samples columns
SB = 256
F32 = jnp.float32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip cannot be read back from the
    # persistent cache; keep these compiles out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, sharding, dtype=F32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _operand(layout, sharding):
    """The operand pytree the solvers bind, as shapes: row slabs of the
    real-sim X ("rows"), row slabs of the news20 X^T, (n, d_pad/128, 128)
    ("cols"), or the padded news20 X the column gather reads ("gather")."""
    if layout == "rows":
        d, n = REAL_SIM
        c_pad = tuning.padded_contraction(n, F32, "rows")
        return RowMajorOperand.tree_unflatten(
            (d, n), [_sds((d, c_pad // 128, 128), sharding)])
    d, n = NEWS20
    if layout == "cols":
        c_pad = tuning.padded_contraction(d, F32, "rows")
        return ColMajorOperand.tree_unflatten(
            (n, d), [_sds((n, c_pad // 128, 128), sharding)])
    c_pad = tuning.padded_contraction(d, F32, "cols")
    return _ColGatherOperand.tree_unflatten(
        (n, d), [_sds((c_pad, -(-n // 128) * 128), sharding)])


def _assert_kernel(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("layout", ["rows", "cols", "gather"])
@pytest.mark.parametrize("method", ["packet", "apply", "matvec"])
def test_gather_kernel_compiles(one_chip, layout, method):
    op = _operand(layout, one_chip)
    flat = _sds((SB,), one_chip, jnp.int32)
    kw = dict(impl="pallas", bm=None, bk=None)
    if method == "packet":
        vec = _sds((op.contraction,), one_chip)
        fn = lambda op, f, u: op.packet(f, u, scale=1.0, reg=0.0,  # noqa: E731
                                        scale_r=1.0, symmetric_skip=True,
                                        **kw)
    elif method == "apply":
        vec = _sds((SB,), one_chip)
        fn = lambda op, f, v: op.apply(f, v, scale=1.0, **kw)  # noqa: E731
    else:
        vec = _sds((op.contraction,), one_chip)
        fn = lambda op, f, t: op.matvec(f, t, scale=1.0, **kw)  # noqa: E731
    _assert_kernel(fn, op, flat, vec)


def test_col_slab_build_fits(one_chip):
    """The news20 X^T slab build compiles to one program whose temporaries
    stay at one block's worth (<= 64 MB), next to raw X and the output."""
    d, n = NEWS20
    c_pad = tuning.padded_contraction(d, F32, "rows")
    compiled = _col_slabs.lower(_sds(NEWS20, one_chip), c_pad).compile()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes == n * c_pad * 4
    assert mem.temp_size_in_bytes <= 64 * 2**20, mem.temp_size_in_bytes


def test_gram_packet_pallas_compiles(one_chip):
    n = REAL_SIM[1]
    _assert_kernel(lambda A, u: gram_packet(A, u, impl="pallas"),
                   _sds((SB, n), one_chip), _sds((n,), one_chip))


@pytest.mark.parametrize("form,layout", [("primal", "rows"),
                                         ("dual", "cols")])
def test_full_solve_fits_one_chip(one_chip, form, layout):
    """The whole s-step solve at full size: kernels inside the scan, and
    nothing beyond the operand itself resident (no second copy of X)."""
    op = _operand(layout, one_chip)
    d, n = op.shape
    plan = SolverPlan(b=16, s=16, impl="pallas")
    compiled = _assert_kernel(
        lambda op, y, idx: s_step_solve(form, plan, op, y, 1e-6, 64,
                                        idx=idx),
        op, _sds((n,), one_chip), _sds((64, 16), one_chip, jnp.int32))
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 0.01 * mem.argument_size_in_bytes


@pytest.mark.parametrize("form,layout", [("primal", "rows"),
                                         ("dual", "cols")])
def test_full_solve_phases(one_chip, form, layout):
    """The engine's phases survive the TPU compiler at full size: every
    working instruction of every loop body of the benchmark's solve (a
    1,280-iteration primal, a 256-iteration dual, b = s = 16) carries
    exactly one ``engine.*`` phase (``tests/_phases.py``), with none left
    to the compiler."""
    from _phases import work_phases
    op = _operand(layout, one_chip)
    d, n = op.shape
    iters = 1280 if form == "primal" else 256
    plan = SolverPlan(b=16, s=16, impl="pallas")
    compiled = _assert_kernel(
        lambda op, y, idx: s_step_solve(form, plan, op, y, 1e-6, iters,
                                        idx=idx),
        op, _sds((n,), one_chip), _sds((iters, 16), one_chip, jnp.int32))
    counts = work_phases(compiled.as_text())
    assert set(counts) == {"loop", "packet", "assemble", "sweep", "update",
                           "metrics"}, counts
    assert counts["loop"] == 1          # the index block's slice


@pytest.mark.parametrize("form,layout", [("primal", "rows"),
                                         ("dual", "cols")])
def test_full_solve_metrics_read_x_once(one_chip, form, layout):
    """At full size, the dual's history reads X in ONE contraction per outer
    step (the outer scan's body, depth 1), none inside the inner scan; the
    primal's metrics (from the maintained alpha) never read it."""
    from _phases import operand_reads
    op = _operand(layout, one_chip)
    d, n = op.shape
    plan = SolverPlan(b=16, s=16, impl="pallas")
    compiled = _assert_kernel(
        lambda op, y, idx: s_step_solve(form, plan, op, y, 1e-6, 64,
                                        idx=idx),
        op, _sds((n,), one_chip), _sds((64, 16), one_chip, jnp.int32))
    reads = operand_reads(compiled.as_text(), op.array.size, "metrics")
    if form == "primal":
        assert not reads, reads
    else:
        assert list(reads.values()) == [1] and next(iter(reads))[0] == 1, \
            reads
