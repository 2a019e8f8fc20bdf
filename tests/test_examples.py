"""The user-facing entry points, run as a user runs them: each solver example
as its own process under the XLA reference and the Pallas interpreter (the
examples assert their own claims, e.g. CA-BCD matching BCD), the chip smoke
run's refusal off the chip, and the import closure of the service and the
fault supervisor."""
import os
import subprocess
import sys

import pytest

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))


def _run(args, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.path.join(_ROOT, "src") + os.pathsep + \
        env.get("PYTHONPATH", "")
    return subprocess.run([sys.executable, *args], cwd=_ROOT, env=env,
                          capture_output=True, text=True, timeout=timeout)


@pytest.mark.parametrize("impl", ["ref", "pallas_interpret"])
@pytest.mark.parametrize("example", ["quickstart", "lasso",
                                     "distributed_ridge"])
def test_example_runs(example, impl):
    proc = _run([os.path.join("examples", f"{example}.py"), "--impl", impl])
    assert proc.returncode == 0, (
        f"{example} --impl {impl}: rc {proc.returncode}\n"
        f"STDOUT:\n{proc.stdout[-3000:]}\nSTDERR:\n{proc.stderr[-3000:]}")


def test_chip_smoke_refuses_without_a_tpu():
    proc = _run(["chip_smoke.py"], timeout=120)
    assert proc.returncode == 2, proc.stderr[-3000:]
    assert "needs a TPU" in proc.stderr and "'cpu'" in proc.stderr, \
        proc.stderr[-3000:]
    assert '"ok"' not in proc.stdout


@pytest.mark.parametrize("module, allowed", [
    pytest.param("repro.serve.solver_service", {"core", "kernels", "serve"},
                 id="solver_service"),
    pytest.param("repro.faults.supervisor",
                 {"core", "kernels", "checkpoint", "faults"}, id="supervisor"),
])
def test_solver_module_imports_stay_in_solver_packages(module, allowed):
    proc = _run(["-c", f"import sys, {module}; print(' '.join(sorted("
                 "{m.split('.')[1] for m in sys.modules "
                 "if m.startswith('repro.')})))"], timeout=120)
    assert proc.returncode == 0, proc.stderr[-3000:]
    loaded = set(proc.stdout.split())
    assert loaded <= allowed, (module, sorted(loaded - allowed))
