"""Benchmark harness aggregator: one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV rows.

    PYTHONPATH=src python -m benchmarks.run [--only MODULE] [--impl I]
    PYTHONPATH=src python -m benchmarks.run --smoke   # tiny shapes, ref mode,
                                                      # writes BENCH_smoke.json

``--smoke`` is the CI perf-trajectory hook (``make bench-smoke``): it runs the
kernel benches on tiny shapes in ref/interpret mode and writes a
``BENCH_smoke.json`` baseline -- wall microseconds per row plus the modeled
HBM bytes/iteration of the panel-free packet vs the gather-then-pack
baseline -- so regressions in either show up as a diff from this PR onward.
Each row records its ``impl``; off-TPU the fused sampled-packet row is
labeled ``wall=ref-proxy`` (the ref backend gathers the panel twice, so its
wall number is not the kernel's wall-clock claim -- only the modeled HBM
ratio is).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

MODULES = ["table1", "table2", "fig2_3", "fig4", "fig5_6", "fig7", "fig8_9",
           "kernels_bench", "prox_bench", "gram_autotune", "guard_bench",
           "serve_bench", "pipeline_bench"]
SMOKE_MODULES = ["kernels_bench", "gram_autotune", "guard_bench",
                 "serve_bench", "pipeline_bench"]
SMOKE_OUT = os.path.join(os.path.dirname(__file__), os.pardir,
                         "BENCH_smoke.json")


def _run_modules(mods, impl, smoke):
    import inspect

    rows, failures = [], 0
    for name in mods:
        t0 = time.time()
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["run"])
            params = inspect.signature(mod.run).parameters
            kw = {}
            if "impl" in params:
                kw["impl"] = impl
            if smoke and "smoke" in params:
                kw["smoke"] = True
            for line in mod.run(**kw):
                print(line, flush=True)
                rows.append(line)
            print(f"# {name} done in {time.time()-t0:.1f}s", file=sys.stderr)
        except Exception:
            failures += 1
            print(f"{name},0.0,BENCH_FAILED", flush=True)
            traceback.print_exc()
    return rows, failures


def _write_smoke_baseline(rows, impl, path=SMOKE_OUT):
    import re

    import jax.numpy as jnp

    from repro.core.cost_model import (dual_operand_tradeoff,
                                       packet_traffic_breakdown)
    from repro.kernels.gram import tuning

    from .kernels_bench import PANEL_SHAPE_SMOKE

    d, n, sb = PANEL_SHAPE_SMOKE
    bm = tuning.pick_tiles(sb, n, jnp.float32)[0]
    parsed = []
    for line in rows:
        name, us, derived = line.split(",", 2)
        # Per-row impl (rows embed "impl=<backend>" in their derived field;
        # e.g. the interpret-mode reference row differs from the harness-wide
        # impl), so the regression gate can tell a wall-clock claim from a
        # ref-proxy of the traffic model.
        m = re.search(r"impl=(\S+)", derived)
        parsed.append({"name": name, "us_per_call": float(us),
                       "impl": m.group(1) if m else impl,
                       "derived": derived})
    baseline = {
        "impl": impl,
        "panel_shape": {"sb": sb, "n": n},
        "hbm_bytes_per_iter": packet_traffic_breakdown(sb, n, itemsize=4,
                                                       bm=bm),
        # The dual-layout trade the column-gather operand makes (modeled;
        # the kernels/dual_resident_* rows carry the measured XLA figures).
        "dual_operand_tradeoff": dual_operand_tradeoff(d, n, sb),
        "rows": parsed,
    }
    with open(path, "w") as f:
        json.dump(baseline, f, indent=2, sort_keys=True)
    print(f"# smoke baseline -> {os.path.abspath(path)}", file=sys.stderr)


def _check_registry() -> None:
    """The harness (and the fig/table modules it drives) selects solvers via
    the (formulation, backend) registry; fail fast if an entry went missing
    rather than part-way through a long sweep."""
    from repro.core import FORMULATIONS, registered_solvers
    from repro.core.engine import BACKENDS
    reg = registered_solvers()
    missing = [(f, bk) for f in FORMULATIONS for bk in BACKENDS
               if (f, bk) not in reg]
    if missing:
        raise SystemExit(f"solver registry incomplete: missing {missing}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--impl", default=None,
                    help="Gram-packet backend forwarded to benches that take "
                         "it: ref | pallas | pallas_interpret")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes, kernel benches only; write "
                         "BENCH_smoke.json")
    args = ap.parse_args()
    if args.only:
        mods = [args.only]
    elif args.smoke:
        mods = SMOKE_MODULES
    else:
        mods = MODULES
    impl = args.impl or ("ref" if args.smoke else None)
    _check_registry()
    print("name,us_per_call,derived")
    rows, failures = _run_modules(mods, impl, args.smoke)
    # Only the canonical smoke set may refresh the committed baseline; a
    # --only run with --smoke still uses tiny shapes but never clobbers it.
    if args.smoke and not args.only and not failures:
        _write_smoke_baseline(rows, impl)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
