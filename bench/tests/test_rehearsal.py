"""Tiny CPU rehearsals of every cell's traffic through the harness (set-up,
window, check), the faults the check must catch, and the control: the
reference at a lower precision in the program's place."""
import json
import os
import subprocess
import sys

import pytest

from conftest import run_tiny
from faults import batched_fault, solve_fault

HERE = os.path.dirname(os.path.abspath(__file__))
ONE_CHIP = ["realsim-primal.solve", "news20-dual.solve", "realsim-primal.serve"]


def test_no_chip_no_result(capsys, cpu_only):
    from bench import harness
    rc = harness.main(["--workload", "realsim-primal.solve", "--seed", "1",
                       "--seconds", "1"], 0.0)
    assert rc == 2
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize("cell", ONE_CHIP)
def test_rehearsal_is_correct(cell, cpu_only):
    run = run_tiny(cell)
    assert run.correct, run.compared
    assert run.attempted > 0 and run.failed == 0
    assert run.compiles_in_window["compiles"] == 0
    assert run.setup_s > 0


def _patch(monkeypatch, cell, kind):
    if cell.endswith("serve"):
        import repro.serve.solver_service as svc
        monkeypatch.setattr(svc, "s_step_solve_batched",
                            batched_fault(svc.s_step_solve_batched, kind))
    else:
        import repro.core as core
        monkeypatch.setattr(core, "s_step_solve",
                            solve_fault(core.s_step_solve, kind))


@pytest.mark.parametrize("cell,kind", [
    (c, k) for c in ONE_CHIP
    for k in ("unchanged", "altered",
              "half_batch" if c.endswith("serve") else "half_block")])
def test_planted_fault_is_not_correct(cell, kind, monkeypatch, cpu_only):
    _patch(monkeypatch, cell, kind)
    run = run_tiny(cell)
    assert not run.correct, run.compared


def _four(fault):
    out = subprocess.run([sys.executable, os.path.join(HERE, "four_device.py"),
                          fault], capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("fault,correct", [
    ("none", True), ("no_exchange", False), ("unchanged", False),
    ("altered", False)])
def test_four_chip_rehearsal(fault, correct):
    res = _four(fault)
    assert res["correct"] is correct, res


@pytest.mark.parametrize("cell", ONE_CHIP)
def test_control_is_not_correct(cell, cpu_only):
    """The control: the reference at a lower matmul precision in the
    program's place.  At this size the single bf16 pass fails the cell's
    limits outright; three bf16 passes (XLA's ``HIGH``, the cell's control
    at full size, where it fails them on the chip: PERF.md) still read
    well clear of the program."""
    run = run_tiny(cell, controls=("bf16_3x", "bf16"))
    one_pass = run.controls["bf16"]
    assert any(c["value"] > c["limit"] for c in one_pass.values()), one_pass
    prog = run.compared["w_rel"]["value"]
    assert run.controls["bf16_3x"]["w_rel"]["value"] > 5 * prog
