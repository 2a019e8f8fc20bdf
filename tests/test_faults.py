"""Fault-injection + recovery tests (DESIGN.md section 7).

Local (single-device) cases run in this process in f32: the full
{nan-packet, bitflip, drop-shard} x {primal, dual, proximal} detection
matrix, the guard's bitwise no-op on clean solves, the jittered SPD solve's
rank-deficient regression, the supervised device-loss restart, and the
snapshot-cadence model.  The sharded matrix and the f64 1e-10 elastic-resume
acceptance run in an 8-device subprocess via tests/_fault_checks.py (the
test_analysis.py pattern -- the main pytest process keeps 1 device).
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine
from repro.core.bcd import ca_bcd, objective
from repro.core.bdcd import ca_bdcd
from repro.core.engine import (GUARD_MAGNITUDE, GUARD_NONFINITE,
                               GUARD_SHARD_LOSS, sample_blocks)
from repro.core.proximal import ca_proximal_bcd, elastic_net_objective
from repro.core.subproblem import solve_spd, solve_spd_jittered
from repro.faults import FaultPlan, solve_supervised

_SCRIPT = os.path.join(os.path.dirname(__file__), "_fault_checks.py")
_SRC = os.path.join(os.path.dirname(__file__), os.pardir, "src")
_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))

D, N, B, S, ITERS = 16, 40, 2, 3, 30
LAM = 1e-2


def _problem(dual=False):
    X = jax.random.normal(jax.random.key(0), (D, N), jnp.float32)
    y = jax.random.normal(jax.random.key(1), (N,), jnp.float32)
    dim = N if dual else D
    idx = sample_blocks(jax.random.key(2), dim, B, ITERS)
    return X, y, idx


SOLVERS = {
    "primal": (ca_bcd, False, lambda X, w, y: objective(X, w, y, LAM)),
    "dual": (ca_bdcd, True, lambda X, w, y: objective(X, w, y, LAM)),
    "proximal": (ca_proximal_bcd, False,
                 lambda X, w, y: elastic_net_objective(X, w, y, LAM, 1e-3)),
}

# bitflip/divergence guards arm off a clean first step, so inject at >= 1.
KIND_STEP_REASON = [("nan_packet", 2, GUARD_NONFINITE),
                    ("bitflip", 1, GUARD_MAGNITUDE),
                    ("drop_shard", 2, GUARD_SHARD_LOSS)]


@pytest.mark.parametrize("form", sorted(SOLVERS))
@pytest.mark.parametrize("kind,step,reason",
                         KIND_STEP_REASON, ids=lambda v: str(v))
def test_local_fault_detected_and_converges(form, kind, step, reason):
    """Every in-scan fault kind x formulation: the guard trips AT the
    injected outer step with the right reason bit, and the degraded solve
    (skip/rescue + s=1 tail) still converges to the clean objective."""
    solve, dual, obj = SOLVERS[form]
    X, y, idx = _problem(dual)
    kw = {"lam1": 1e-3} if form == "proximal" else {}
    clean = solve(X, y, LAM, B, S, ITERS, None, idx=idx, **kw)
    res = solve(X, y, LAM, B, S, ITERS, None, idx=idx, guard=True,
                fault=FaultPlan(kind, step=step), **kw)
    m = {k: np.asarray(jax.device_get(v)).item()
         for k, v in res.metrics.items()}
    assert m["guard_trips"] >= 1, m
    assert m["guard_first_trip"] == step, m
    assert int(m["guard_first_reason"]) & reason, m
    # rung two engaged: the remaining iterations ran at s=1
    assert m["s1_tail_from_outer"] == step, m
    assert m["s1_tail_from_iter"] == step * S, m
    # Converged near the clean solve: the fault cost at most one outer step
    # of progress (skip) plus the tail's ordering rounding -- NOT a blowup.
    # (Absolute optimality is the clean solver tests' business; the dual in
    # particular converges slowly at this tiny problem scale.)
    o_clean = float(obj(X, clean.w, y))
    o_fault = float(obj(X, res.w, y))
    assert np.isfinite(o_fault)
    assert o_fault <= o_clean * 1.25 + 1e-6, (o_fault, o_clean)


@pytest.mark.parametrize("form", sorted(SOLVERS))
def test_guard_is_bitwise_noop_on_clean_solves(form):
    """Arming the guard on a healthy solve changes NOTHING: same iterates
    bit-for-bit, zero trips -- detection is free until something breaks."""
    solve, dual, _ = SOLVERS[form]
    X, y, idx = _problem(dual)
    kw = {"lam1": 1e-3} if form == "proximal" else {}
    plain = solve(X, y, LAM, B, S, ITERS, None, idx=idx, **kw)
    guarded = solve(X, y, LAM, B, S, ITERS, None, idx=idx, guard=True, **kw)
    np.testing.assert_array_equal(np.asarray(plain.w), np.asarray(guarded.w))
    np.testing.assert_array_equal(np.asarray(plain.alpha),
                                  np.asarray(guarded.alpha))
    m = {k: np.asarray(v).item() for k, v in guarded.metrics.items()}
    assert m["guard_trips"] == 0 and m["guard_first_trip"] == -1, m


# ---------------------------------------------------------------------------
# satellite: NaN-free SPD solve for singular blocks
# ---------------------------------------------------------------------------

def test_solve_spd_jittered_rank_deficient_block():
    """A duplicate-index block at lam=0 makes the sb x sb matrix exactly
    singular: plain solve_spd emits NaN (the pre-PR-7 breakage), the
    jittered ladder returns a finite solution and flags the jitter."""
    s, b = 4, 2
    X, _, _ = _problem()
    flat = jnp.array([3, 3, 3, 3, 5, 5, 5, 5])    # rank-2 Gram, sb=8
    Y = X[flat, :]
    A = Y @ Y.T / N                               # lam = 0: singular
    rhs = jnp.ones((s * b,), jnp.float32)
    assert not bool(jnp.all(jnp.isfinite(solve_spd(A, rhs))))
    x, jitter, ok = solve_spd_jittered(A, rhs)
    assert bool(jnp.all(jnp.isfinite(x)))
    assert bool(ok)
    assert float(jitter) > 0


def test_guarded_solve_survives_duplicate_indices_at_lam0():
    """End-to-end regression: the same rank-deficient duplicate-index stream
    at lam=0, s=4 NaNs the unguarded CA solve; the guard rescues it."""
    X, y, _ = _problem()
    idx = jnp.tile(jnp.array([[3, 3], [5, 5]], jnp.int32), (6, 1))  # 12 iters
    bad = ca_bcd(X, y, 0.0, B, 4, 12, None, idx=idx)
    assert not bool(jnp.all(jnp.isfinite(bad.w)))
    res = ca_bcd(X, y, 0.0, B, 4, 12, None, idx=idx, guard=True)
    assert bool(jnp.all(jnp.isfinite(res.w)))
    m = {k: np.asarray(v).item() for k, v in res.metrics.items()}
    assert m["guard_trips"] >= 1, m
    assert float(objective(X, res.w, y, 0.0)) < float(
        objective(X, jnp.zeros_like(res.w), y, 0.0))


# ---------------------------------------------------------------------------
# supervised solves (local backend; the sharded/elastic path is subprocess)
# ---------------------------------------------------------------------------

def test_supervised_local_device_loss_resumes(tmp_path):
    """Device loss mid-solve: the supervisor restores the newest snapshot
    and the finished solve matches the uninterrupted one."""
    X, y, idx = _problem()
    fault = FaultPlan("device_loss", step=4)
    res = solve_supervised("primal", "local", X, y, LAM, B, S, ITERS, None,
                           idx=idx, ckpt_dir=str(tmp_path), fault=fault)
    assert res.metrics["restarts"] == 1
    assert res.metrics["resumed_from_iter"] > 0
    clean = ca_bcd(X, y, LAM, B, S, ITERS, None, idx=idx)
    np.testing.assert_allclose(np.asarray(res.w), np.asarray(clean.w),
                               rtol=0, atol=1e-5)


def test_supervised_restart_budget_exhausted(tmp_path):
    """A loss injected at step 0 with max_restarts=0 must surface, not loop."""
    from repro.faults import DeviceLostError
    X, y, idx = _problem()
    with pytest.raises(DeviceLostError):
        solve_supervised("primal", "local", X, y, LAM, B, S, ITERS, None,
                         idx=idx, ckpt_dir=str(tmp_path), max_restarts=0,
                         fault=FaultPlan("device_loss", step=0))


def test_plan_solver_mesh_caps_at_devices_present():
    """A restart asks for the survivors' count; the mesh never holds more
    devices than the process has, and never fewer than one."""
    from repro.faults.supervisor import plan_solver_mesh
    present = len(jax.devices())
    for asked in (0, 1, present, present + 7):
        mesh = plan_solver_mesh(asked)
        assert mesh.devices.shape == (max(1, min(asked, present)),), asked
        assert mesh.axis_names == ("shards",)


def test_plan_solver_mesh_names_the_given_axis():
    from repro.faults.supervisor import plan_solver_mesh
    mesh = plan_solver_mesh(1, "rows")
    assert mesh.axis_names == ("rows",)
    assert mesh.axis_types == (jax.sharding.AxisType.Auto,)


def test_snapshot_cadence_model():
    from repro.core.cost_model import TPU_V5E_ICI, snapshot_cadence
    out = snapshot_cadence(TPU_V5E_ICI, d=1 << 16, n=1 << 20, P=64, b=8,
                           s=16, mtbf_outer=1e6)
    assert out["cadence"] >= 1
    assert 0 < out["overhead"] < 1
    # rarer failures -> snapshot less often
    rare = snapshot_cadence(TPU_V5E_ICI, d=1 << 16, n=1 << 20, P=64, b=8,
                            s=16, mtbf_outer=1e8)
    assert rare["cadence"] > out["cadence"]
    with pytest.raises(ValueError):
        snapshot_cadence(TPU_V5E_ICI, d=4, n=8, P=1, b=1, s=1, mtbf_outer=0)


# ---------------------------------------------------------------------------
# FaultPlan validation
# ---------------------------------------------------------------------------

def test_fault_plan_validation():
    with pytest.raises(ValueError):
        FaultPlan("meteor_strike", step=0)
    with pytest.raises(ValueError):
        FaultPlan("nan_packet", step=-1)
    with pytest.raises(ValueError):
        engine.SolverPlan(b=2, s=2, fault=object())   # duck-type check
    with pytest.raises(ValueError):
        engine.SolverPlan(b=2, s=2, guard_boost=1.0)


# ---------------------------------------------------------------------------
# sharded matrix + f64 elastic resume: 8-device subprocess checks
# ---------------------------------------------------------------------------

def _run(check: str):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(_SRC) + os.pathsep + \
        os.path.dirname(__file__) + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, _SCRIPT, check], env=env,
                          capture_output=True, text=True, timeout=1200,
                          cwd=_ROOT)
    assert proc.returncode == 0, (
        f"{check} failed:\nSTDOUT:\n{proc.stdout[-3000:]}\n"
        f"STDERR:\n{proc.stderr[-3000:]}")
    assert f"{check} OK" in proc.stdout


def test_sharded_fault_matrix():
    """{nan, bitflip, drop-shard} x {primal, dual, proximal} on an 8-device
    mesh: detected at the injected step, converged objective."""
    _run("fault_matrix_sharded")


def test_pipelined_fault_parity():
    """Guard trips under the pipelined ring wire degrade identically to the
    psum backend: same reason bits, same trip step, same s=1 tail."""
    _run("fault_parity_pipelined")


def test_supervised_elastic_resume_sharded():
    """The acceptance gate: injected device loss, resume on a smaller mesh
    from the newest snapshot, f64 objective matches the uninterrupted solve
    to 1e-10 on even AND ragged schedules."""
    _run("supervised_resume_sharded")


def test_supervised_resume_local_f64():
    _run("supervised_resume_local")
