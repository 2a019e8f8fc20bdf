"""The 4-chip cell at a tiny size on four virtual CPU devices, in a process
of its own: ``python four_device.py [none|no_exchange|unchanged|altered]``.
Prints the run's JSON summary."""
import json
import os
import sys

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from conftest import run_tiny  # noqa: E402

if __name__ == "__main__":
    import jax
    import repro.core as core
    from faults import solve_fault
    fault = sys.argv[1]
    if fault == "no_exchange":
        # the packet all-reduce left out: each chip keeps its own share
        jax.lax.psum = lambda x, axis_name, **kw: x
    elif fault != "none":
        core.s_step_solve_sharded = solve_fault(core.s_step_solve_sharded,
                                                fault)
    run = run_tiny("realsim-primal.solve-4chip")
    print(json.dumps({"correct": run.correct, "compared": run.compared,
                      "failed": run.failed}))
