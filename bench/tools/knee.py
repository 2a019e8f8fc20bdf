"""Knee sweep of the solve service, on the chip, in one process: set up the
serve cell once, then offer each rate for ``--seconds`` and report the 95th
percentile latency, the completed rate and whether the backlog grew.  The
knee is the highest rate served without a growing backlog; the serve
cell's traffic offers a fixed share of it.

    python3 bench/tools/knee.py --workload realsim-primal.serve \\
        --rates 20,30,40 --seconds 10 --seed 1
"""
import argparse
import json
import os
import sys
import time

if __name__ == "__main__":
    ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness
    from bench.loops import loop
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="realsim-primal.serve")
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    rates = [float(r) for r in args.rates.split(",")]
    spec, cell, cfg, traffic, limits = harness.load_cell(args.workload)
    harness.set_compile_cache()
    import jax
    import numpy as np
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"needs a TPU, found {devs}")
    top = dict(traffic, rate_per_s=max(rates))
    drv = loop(top["kind"])(cfg, top, args.seed, devs[:1], cfg["impl"])
    run = harness.Run(cell, cfg, top, args.seed)
    drv.setup(run, args.seconds)
    print(json.dumps({"setup": run.setup_parts}), flush=True)
    for rate in rates:
        drv.traffic = dict(traffic, rate_per_s=rate)
        drv.times, drv.lams, drv.n_pre = drv._schedule(args.seconds)
        r = harness.Run(cell, cfg, drv.traffic, args.seed)
        t0 = time.perf_counter()
        drv.window(r, args.seconds, lambda: None, lambda: None)
        w = r.window
        lat = w["latency_s"] or [float("inf")]
        bl = [o for s, o in w["backlog"] if 0 <= s < args.seconds]
        third = max(1, len(bl) // 3)
        print(json.dumps({
            "rate_per_s": rate,
            "p50_ms": 1e3 * float(np.percentile(lat, 50)),
            "p95_ms": 1e3 * float(np.percentile(lat, 95)),
            "completed_per_s": w["completed_in_window"] / args.seconds,
            "attempted": r.attempted, "failed": r.failed,
            "step_ms_mean": 1e3 * float(np.mean(w["step_s"])),
            "backlog_first_third": float(np.mean(bl[:third])),
            "backlog_last_third": float(np.mean(bl[-third:])),
            "wall_s": time.perf_counter() - t0}), flush=True)
