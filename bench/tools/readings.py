"""Readings for the limits of a cell's check, on the chip, in one process:
for each seed, one short run of the cell (the program's readings) and the
same check with the reference at each control precision in the program's
place (the controls' readings).

    python3 bench/tools/readings.py --workload <cell> --seeds 1,2,3 \\
        [--controls bf16_3x,bf16] [--seconds 2]

Prints one JSON line per seed.  The limits in ``limits/<cell>.json`` are
set between the largest program reading and the smallest control reading.
"""
import argparse
import json
import os
import sys
import time

if __name__ == "__main__":
    T0 = time.perf_counter()
    ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", default="")
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args()
    spec, cell, cfg, traffic, limits = harness.load_cell(args.workload)
    harness.set_compile_cache()
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        sys.exit(f"needs {cell['chips']} TPU chip(s), found {devs}")
    controls = [c for c in args.controls.split(",") if c]
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        run = harness.execute(cell, cfg, traffic, limits, seed, args.seconds,
                              False, devs[:cell["chips"]], cfg["impl"],
                              time.perf_counter(), controls=controls)
        print(json.dumps({"workload": cell["name"], "seed": seed,
                          "program": run.compared, "failed": run.failed,
                          "controls": run.controls,
                          "wall_s": time.perf_counter() - t0}), flush=True)
