"""The program's own tracing: the engine's phases as named scopes inside the
compiled solve, and the solve service's host spans and counters.

Phases are read back from compiled HLO (``tests/_phases.py``): every
working instruction of every loop body carries exactly one ``engine.*``
phase.  Here on the CPU at tiny sizes for the local and batched drivers,
and in an 8-device subprocess for the sharded psum and ring drivers; the
full-width v5e compiles are checked in ``tests/test_tpu_compile.py``, the
one file that loads the TPU compiler."""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _phases import work_phases
from repro.core import (SolverPlan, TenantBatch, s_step_solve,
                        s_step_solve_batched)
from repro.core.hlo_analysis import (engine_phase_map, op_phase,
                                     parse_named_ops)
from repro.kernels.gram import ColMajorOperand, RowMajorOperand

BODY = {"loop", "packet", "assemble", "sweep", "update", "metrics"}

# A line of a compiled dual solve's HLO (JAX 0.9, CPU): the op_name of its
# metadata starts past the 200 characters ``HloOp.line`` keeps.
FIXTURE_LINE = (
    '  %copy_dot_fusion = f32[1024]{0} fusion(%get-tuple-element.501, '
    '%get-tuple-element.502, %get-tuple-element.500, %get-tuple-element.499, '
    '%copy.35), kind=kLoop, calls=%fused_computation.14, metadata={op_name='
    '"jit(<lambda>)/engine.loop/while/body/closed_call/engine.metrics/while/'
    'body/closed_call/engine.update/km,m->k/dot_general" stack_frame_id=121}')


def test_op_name_parsed_from_full_line():
    (op,) = parse_named_ops(FIXTURE_LINE)
    assert op.opcode == "fusion" and op.result_name == "%copy_dot_fusion"
    assert len(op.line) == 200 and "op_name" not in op.line
    assert op.op_name.endswith("engine.update/km,m->k/dot_general")
    assert op_phase(op.op_name) == "update"     # innermost scope wins
    (bare,) = parse_named_ops("  %copy.9 = f32[4]{0} copy(%x)")
    assert bare.op_name is None and op_phase(bare.op_name) is None


@pytest.mark.parametrize("op_name,phase", [
    ("jit(f)/engine.loop/while/body/engine.sweep/jit(_cholesky)/cholesky",
     "sweep"),
    ("jit(f)/engine.reduce/engine.packet/jit(gram_packet_sampled_pallas)/"
     "pallas_call", "packet"),
    ("jit(f)/vmap(engine.packet)/dot_general", "packet"),
    ("jit(f)/engine.loop/while/body/engine.update/mul;while/body/closed_call",
     "update"),
    ("jit(f)/engine.loop/while/body/add", "loop"),
    ("jit(f)/while/cond/lt", None),
    ("jit(f)/my_engine.packet/mul", None),
])
def test_op_phase(op_name, phase):
    assert op_phase(op_name) == phase


def test_unnamed_fusion_takes_its_fused_phase():
    text = "\n".join([
        "%fused_computation.15 (p: f32[4]) -> f32[4] {",
        '  %c = f32[] constant(0), metadata={op_name="jit(f)/engine.sweep/'
        'cholesky"}',
        "  ROOT %s = f32[4]{0} select(%p, %c, %p)",
        "}",
        "ENTRY %main (x: f32[4]) -> f32[4] {",
        "  ROOT %broadcast_select_fusion.2 = f32[4]{0} fusion(%x), "
        "kind=kLoop, calls=%fused_computation.15",
        "}"])
    assert engine_phase_map(text) == {"c": "sweep", "s": None,
                                      "broadcast_select_fusion.2": "sweep"}


def test_unnamed_fusion_phase_skips_merged_constant():
    """A fused constant names whichever op XLA's constant merging kept
    (here the outer loop's); the fusion's working instructions decide."""
    text = "\n".join([
        "%fused_computation.27 (p: f32[16,60]) -> f32[2,1,8,64] {",
        '  %c = f32[] constant(0), metadata={op_name="jit(f)/engine.loop/'
        'while/body/closed_call"}',
        '  %pad.20 = f32[16,64]{1,0} pad(%p, %c), padding=0_0x0_4, '
        'metadata={op_name="jit(f)/engine.loop/while/body/closed_call/'
        'engine.metrics/jit(_pad)/pad"}',
        "  ROOT %b = f32[2,1,8,64]{3,2,1,0} bitcast(%pad.20)",
        "}",
        "ENTRY %main (x: f32[16,60]) -> f32[2,1,8,64] {",
        "  ROOT %pad_bitcast_fusion.2 = f32[2,1,8,64]{3,2,1,0} fusion(%x), "
        "kind=kLoop, calls=%fused_computation.27",
        "}"])
    assert engine_phase_map(text)["pad_bitcast_fusion.2"] == "metrics"


def _regression(d, n):
    X = jax.random.normal(jax.random.key(0), (d, n), jnp.float32)
    return X, X.T @ jnp.ones((d,), jnp.float32)


@pytest.mark.parametrize("form,kw", [
    ("primal", {}), ("dual", {}), ("primal", {"guard": True,
                                              "track_cond": True}),
    ("primal", {"impl": "pallas_interpret"})])
def test_local_solve_phases(form, kw):
    d, n = (64, 512) if form == "primal" else (512, 64)
    X, y = _regression(d, n)
    op = (RowMajorOperand if form == "primal" else ColMajorOperand).of(X)
    plan = SolverPlan(b=4, s=4, **{"impl": "ref", **kw})
    dim = d if form == "primal" else n
    idx = jnp.arange(18 * 4, dtype=jnp.int32).reshape(18, 4) * 5 % dim
    text = jax.jit(lambda op, y, idx: s_step_solve(
        form, plan, op, y, 1e-3, 18, idx=idx)).lower(op, y, idx).compile(
    ).as_text()
    counts = work_phases(text, strict=False)
    assert set(counts) - {"compiler-made"} == BODY, counts
    # init_carry (the dual's X alpha) runs outside the scan, as bind.
    assert "bind" in engine_phase_map(text).values() or form == "primal"


def test_batched_solve_phases():
    X, y = _regression(64, 512)
    T = 4
    ys = jnp.stack([y * (t + 1) for t in range(T)])
    lams = jnp.full((T,), 1e-3, jnp.float32)
    plan = SolverPlan(b=4, s=4, impl="ref", tenants=T)
    idx = jnp.arange(18 * 4, dtype=jnp.int32).reshape(18, 4) * 5 % 64
    text = jax.jit(lambda op, ys, lams, idx: s_step_solve_batched(
        "primal", plan, op, TenantBatch(ys, lams, tol=1e-4), 18,
        idx=idx)).lower(RowMajorOperand.of(X), ys, lams, idx).compile(
    ).as_text()
    counts = work_phases(text, strict=False)
    # bind: the per-tenant init map; metrics: the in-scan retirement test.
    assert set(counts) - {"compiler-made"} == BODY | {"bind"}, counts


def test_sharded_drivers_phases():
    """psum and ring wires, single and batched, on 8 virtual devices
    (``tests/dist_checks.py engine_phases``)."""
    from test_distributed import _run
    _run("engine_phases")


# ------------------------------------------------------------ the service --

def _service(d=32, n=256, requests=3):
    from repro.serve.solver_service import SolverService, SolverServiceConfig
    X, _ = _regression(d, n)
    svc = SolverService(X, SolverPlan(b=4, s=2, impl="ref"), "primal",
                        SolverServiceConfig(slots=8, min_bucket=4,
                                            chunk_iters=8, max_iters=16))
    rng = np.random.default_rng(0)
    for _ in range(requests):
        svc.submit(rng.standard_normal(n), 1e-2)
    return svc


def test_service_step_spans(tmp_path):
    svc = _service()
    svc.step()                                  # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        svc.step()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                        recursive=True)
    spans = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("solver_service."):
                    spans[ev.name] = (ev.start_ns, ev.start_ns + ev.duration_ns)
    phases = ("admit", "sample", "chunk", "copy_out", "residual", "retire")
    assert set(spans) == {"solver_service.step"} | {
        f"solver_service.{p}" for p in phases}
    lo, hi = spans["solver_service.step"]
    ends = [spans[f"solver_service.{p}"] for p in phases]
    assert all(lo <= s <= e <= hi for s, e in ends)
    assert all(a[1] <= b[0] for a, b in zip(ends, ends[1:]))   # in order


def test_service_stats_count_the_round_trip():
    d, n, f32 = 32, 256, 4
    svc = _service(d, n, requests=3)
    assert svc.stats() == dict.fromkeys(
        ("steps", "admitted", "retired", "h2d_bytes", "d2h_bytes",
         "chunk_compiles"), 0)
    bucket = 4                      # 3 live rows in the smallest bucket
    # Each step copies the bucket's ys, lams, ws and alphas to the device
    # twice (the chunk, the residual) plus the chunk's active mask, and
    # brings back ws, alphas and one residual per row.
    h2d = 2 * bucket * (n + 1 + d + n) * f32 + bucket
    d2h = bucket * (d + n + 1) * f32
    svc.step()
    assert svc.stats() == {"steps": 1, "admitted": 3, "retired": 0,
                           "h2d_bytes": h2d, "d2h_bytes": d2h,
                           "chunk_compiles": 2}
    svc.step()                      # 16 iterations: every request retires
    svc.step()                      # an empty table: nothing to copy
    assert svc.stats() == {"steps": 3, "admitted": 3, "retired": 3,
                           "h2d_bytes": 2 * h2d, "d2h_bytes": 2 * d2h,
                           "chunk_compiles": 2}

