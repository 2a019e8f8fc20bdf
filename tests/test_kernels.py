"""Pallas kernel validation: interpret-mode execution vs the pure-jnp oracle,
swept over shapes (tile multiples and ragged) and dtypes, plus hypothesis
(skipped with a reason when hypothesis is not installed)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hyp import given, st

from repro.kernels.gram import (ColMajorOperand, gram, gram_packet,
                                gram_packet_ref, gram_packet_sampled,
                                gram_packet_sampled_cols_ref,
                                gram_packet_sampled_ref, panel_apply,
                                panel_apply_cols_ref, tuning)
from repro.kernels.gram.operands import _ColGatherOperand

SHAPES = [(128, 512), (64, 300), (96, 1024), (8, 128), (130, 700), (256, 256)]
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
def test_gram_packet_matches_ref(shape, dtype):
    m, n = shape
    A = jax.random.normal(jax.random.key(0), (m, n), dtype)
    u = jax.random.normal(jax.random.key(1), (n,), dtype)
    G1, r1 = gram_packet(A, u, scale=1.0 / n, reg=0.01,
                         impl="pallas_interpret")
    G0, r0 = gram_packet_ref(A, u, 1.0 / n, 0.01)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(G1, G0, rtol=tol, atol=tol)
    np.testing.assert_allclose(r1, r0, rtol=tol, atol=tol)


def test_gram_symmetric_skip_equals_full():
    A = jax.random.normal(jax.random.key(2), (128, 512), jnp.float32)
    u = jnp.zeros((512,), jnp.float32)
    G_skip, _ = gram_packet(A, u, impl="pallas_interpret", symmetric_skip=True)
    G_full, _ = gram_packet(A, u, impl="pallas_interpret", symmetric_skip=False)
    np.testing.assert_allclose(G_skip, G_full, rtol=1e-6, atol=1e-6)


def test_gram_output_symmetric():
    A = jax.random.normal(jax.random.key(3), (192, 384), jnp.float32)
    G = gram(A, scale=0.5, reg=1.0, impl="pallas_interpret")
    np.testing.assert_allclose(G, G.T, rtol=0, atol=0)  # exact by construction


def test_reg_on_diagonal_only():
    A = jnp.zeros((64, 128), jnp.float32)
    G = gram(A, reg=2.5, impl="pallas_interpret")
    np.testing.assert_allclose(G, 2.5 * jnp.eye(64), atol=0)


@given(m=st.integers(4, 80), n=st.integers(16, 400), seed=st.integers(0, 999))
def test_gram_property_ragged_shapes(m, n, seed):
    A = jax.random.normal(jax.random.key(seed), (m, n), jnp.float32)
    u = jax.random.normal(jax.random.key(seed + 1), (n,), jnp.float32)
    G1, r1 = gram_packet(A, u, scale=1.0 / n, reg=0.1, impl="pallas_interpret")
    G0, r0 = gram_packet_ref(A, u, 1.0 / n, 0.1)
    np.testing.assert_allclose(G1, G0, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(r1, r0, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("shape", [(96, 512), (40, 300), (13, 128)])
@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
def test_gram_packet_sampled_matches_ref(shape, dtype):
    """The index-prefetched gather kernel vs the jnp oracle, including
    out-of-order and duplicate indices and ragged (m, n)."""
    m, n = shape
    d = 2 * max(m, 16)
    X = jax.random.normal(jax.random.key(10), (d, n), dtype)
    u = jax.random.normal(jax.random.key(11), (n,), dtype)
    flat = jax.random.randint(jax.random.key(12), (m,), 0, d, jnp.int32)
    G1, r1 = gram_packet_sampled(X, flat, u, scale=1.0 / n, reg=0.01,
                                 impl="pallas_interpret")
    G0, r0 = gram_packet_sampled_ref(X, flat, u, 1.0 / n, 0.01)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(G1, G0, rtol=tol, atol=tol)
    np.testing.assert_allclose(r1, r0, rtol=tol, atol=tol)


@pytest.mark.parametrize("cols", [ColMajorOperand, _ColGatherOperand],
                         ids=["slabs", "gather"])
@pytest.mark.parametrize("shape", [(96, 512), (40, 300), (13, 128)])
@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
def test_gram_packet_sampled_cols_matches_ref(shape, dtype, cols):
    """Both column operands vs the jnp oracle: m sampled columns of a (d, n)
    X -- the row kernel on the slabs of X^T, the lane-slab column-gather
    kernel on X's ORIGINAL layout -- including out-of-order and duplicate
    indices and ragged (m, d, n)."""
    m, d = shape
    pool = 2 * max(m, 16) + 5           # ragged column count (n % 128 != 0)
    X = jax.random.normal(jax.random.key(20), (d, pool), dtype)
    u = jax.random.normal(jax.random.key(21), (d,), dtype)
    flat = jax.random.randint(jax.random.key(22), (m,), 0, pool, jnp.int32)
    G1, r1 = gram_packet_sampled(cols(X), flat, u, scale=1.0 / d,
                                 reg=0.01, impl="pallas_interpret")
    G0, r0 = gram_packet_sampled_cols_ref(X, flat, u, 1.0 / d, 0.01)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(G1, G0, rtol=tol, atol=tol)
    np.testing.assert_allclose(r1, r0, rtol=tol, atol=tol)


@pytest.mark.parametrize("cols", [ColMajorOperand, _ColGatherOperand],
                         ids=["slabs", "gather"])
def test_panel_apply_cols_matches_ref(cols):
    # f32 (this module runs without the x64 fixture): f32-level tolerances.
    d, pool = 31, 200
    X = jax.random.normal(jax.random.key(23), (d, pool), jnp.float32)
    flat = jnp.asarray([3, 3, 0, 199, 8], jnp.int32)
    v = jax.random.normal(jax.random.key(24), (5,), jnp.float32)
    a0 = 0.7 * X[:, flat] @ v
    np.testing.assert_allclose(panel_apply_cols_ref(X, flat, v, 0.7), a0,
                               rtol=1e-5, atol=1e-5)
    for impl in ("ref", "pallas_interpret"):
        a1 = panel_apply(cols(X), flat, v, scale=0.7, impl=impl)
        np.testing.assert_allclose(a1, a0, rtol=1e-5, atol=1e-5)


def test_gram_only_kernel_skips_residual():
    """ops.gram dispatches to the residual-free kernel and still matches the
    packet's G (satellite: no zeros-u wasted work)."""
    A = jax.random.normal(jax.random.key(13), (96, 384), jnp.float32)
    G = gram(A, scale=0.5, reg=1.0, impl="pallas_interpret")
    Gp, _ = gram_packet(A, jnp.zeros((384,), jnp.float32), scale=0.5, reg=1.0,
                        impl="pallas_interpret")
    np.testing.assert_allclose(G, Gp, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(G, G.T, rtol=0, atol=0)


def test_tuning_table_pick_and_override():
    """pick_tiles: table hits and the clamped default; explicit (bm, bk)
    still wins through the dispatch layer (tiles the chip accepts: a whole-
    array bm, a 128-multiple bk)."""
    bm, bk = tuning.pick_tiles(13, 70, jnp.float32)
    assert 16 % bm == 0 or bm <= 16   # never exceeds the padded operand
    assert bk <= 128
    assert tuning.pick_tiles(128, 32768, jnp.float32) == (128, 1024)
    A = jax.random.normal(jax.random.key(14), (24, 200), jnp.float32)
    u = jax.random.normal(jax.random.key(15), (200,), jnp.float32)
    G0, r0 = gram_packet(A, u, impl="pallas_interpret")           # autotuned
    G1, r1 = gram_packet(A, u, impl="pallas_interpret", bm=24, bk=128)
    # different tiles reorder the f32 accumulation; values agree to f32 level
    np.testing.assert_allclose(G1, G0, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(r1, r0, rtol=2e-5, atol=2e-5)


def test_tuning_register_and_snapshot():
    snap = tuning.table_snapshot()
    try:
        tuning.register_table({"8,256,float32": (8, 256)})
        assert tuning.pick_tiles(8, 256, jnp.float32) == (8, 256)
    finally:
        tuning._TABLE.clear()
        tuning.register_table(snap)


def test_tuning_layout_dimension():
    """PR-5 satellite: table keys carry the operand layout.  Legacy
    three-field keys load unchanged and mean row-major; a cols entry only
    answers cols lookups; unknown layouts fail fast."""
    snap = tuning.table_snapshot()
    try:
        tuning.register_table({"8,512,float32": (8, 512)})      # legacy key
        assert tuning.pick_tiles(8, 512, jnp.float32) == (8, 512)
        tuning.register_table({"8,512,float32,cols": (8, 128)})
        assert tuning.pick_tiles(8, 512, jnp.float32, layout="cols") == (8, 128)
        # the rows entry is untouched by the cols registration
        assert tuning.pick_tiles(8, 512, jnp.float32) == (8, 512)
        # cols heuristic fallback clamps to the padded operand
        bm, bk = tuning.pick_tiles(5, 24, jnp.float32, layout="cols")
        assert bm <= 8 and bk <= 24
    finally:
        tuning._TABLE.clear()
        tuning.register_table(snap)
    with pytest.raises(ValueError, match="unknown operand layout"):
        tuning.pick_tiles(8, 512, jnp.float32, layout="diagonal")
    with pytest.raises(ValueError, match="unknown operand layout"):
        tuning.register_table({"8,512,float32,diagonal": (8, 64)})


def test_solver_uses_kernel_consistently():
    """ops.gram_packet (ref path) equals the inline Gram the solvers build."""
    A = jax.random.normal(jax.random.key(4), (40, 200), jnp.float32)
    u = jax.random.normal(jax.random.key(5), (200,), jnp.float32)
    n = A.shape[1]
    G, r = gram_packet(A, u, scale=1.0 / n, impl="ref")
    np.testing.assert_allclose(G, A @ A.T / n, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(r, A @ u / n, rtol=1e-5, atol=1e-5)
