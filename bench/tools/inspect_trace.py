"""One traced run of a cell that keeps the raw trace and describes it:
planes, lines, event counts and the most frequent names per line, and (when
the reduction can read it) a compact copy of the reduced trace for tests.

    python3 bench/tools/inspect_trace.py --workload <cell> --seed 1 \\
        --seconds 2 --out <dir>
"""
import argparse
import collections
import glob
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
    from bench.peaks import peaks
    from bench.trace import op_name
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)
    spec, cell, cfg, traffic, limits = harness.load_cell(args.workload)
    harness.set_compile_cache()
    import jax
    devs = jax.devices()[:cell["chips"]]
    err = None
    try:
        run = harness.execute(cell, cfg, traffic, limits, args.seed,
                              args.seconds, True, devs, cfg["impl"], T0,
                              peak=peaks(devs[0].device_kind),
                              keep_trace=args.out)
    except Exception as e:          # describe the raw trace all the same
        err = repr(e)
        run = None
    path = glob.glob(os.path.join(args.out, "*.xplane.pb"))[0]
    pd = jax.profiler.ProfileData.from_file(path)
    desc = []
    for plane in pd.planes:
        lines = []
        for ln in plane.lines:
            names = collections.Counter(op_name(e.name)[:160]
                                        for e in ln.events)
            evs = list(ln.events)
            lines.append({"line": ln.name, "events": len(evs),
                          "first_ns": evs[0].start_ns if evs else None,
                          "top": names.most_common(25)})
        desc.append({"plane": plane.name, "lines": lines})
    os.remove(path)                 # raw traces are too large to bring back
    with open(os.path.join(args.out, "describe.json"), "w") as f:
        json.dump({"error": err, "planes": desc}, f, indent=1)
    if run is not None:
        with open(os.path.join(args.out, "reduced.json"), "w") as f:
            f.write(run.trace.to_json())
        out = {"correct": run.correct, "compared": run.compared,
               "per_layer": harness.metrics_of(spec, cell["name"], "metrics",
                                               run),
               "busy_s": run.trace.busy_s(), "window_s": run.trace.window_s,
               "top_ops": run.trace.top_ops(10),
               "idle_gaps": run.trace.idle_by_span(10)}
        print(json.dumps(out), flush=True)
    else:
        print(json.dumps({"error": err}), flush=True)
