"""Multi-device (8 simulated CPU devices) integration tests.  Each case runs
in a subprocess so the main pytest world stays at 1 device (the dry-run's 512
likewise lives in its own process)."""
import os
import subprocess
import sys

_SCRIPT = os.path.join(os.path.dirname(__file__), "dist_checks.py")
_SRC = os.path.join(os.path.dirname(__file__), os.pardir, "src")


def _run(check: str):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(_SRC) + os.pathsep + \
        os.path.dirname(__file__) + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, _SCRIPT, check], env=env,
                          capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, (
        f"{check} failed:\nSTDOUT:\n{proc.stdout[-3000:]}\n"
        f"STDERR:\n{proc.stderr[-3000:]}")
    assert f"{check} OK" in proc.stdout


def test_distributed_solver_equivalence():
    _run("solver_equivalence")


def test_pipelined_wire_schedule():
    """Pipelined backend == psum backend to f64 ~1e-12 (reduction order
    differs, so not bit-for-bit) for every registered formulation, single +
    batched, with the declared collective-permute ring machine-counted."""
    _run("pipelined_wire")


def test_collective_count_reduction_by_s():
    _run("collective_counts")


def test_collective_count_pallas_lowering():
    """One all-reduce per outer iteration on the kernel-backend lowering
    (interpret off-TPU; the real Mosaic lowering on TPU)."""
    _run("collective_counts_pallas")


def test_batched_collectives_independent_of_tenants():
    """T-tenant batched lowering: exactly H = ceil(iters/s) all-reduces at
    T in {1, 8, 64}, per-step payload sb^2 + T*sb words (shared Gram not
    scaled by T)."""
    _run("batched_collectives")
