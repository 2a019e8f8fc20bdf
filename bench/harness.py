"""One run of one cell: set-up, the measured window, the reference check,
and the result line.

Everything that belongs to one cell is found by name: the configuration
``configs/<config>.json``, the traffic ``traffic/<traffic>.json`` (whose
``kind`` names the loop in ``loops/``), the limits of the check
``limits/<cell>.json``, and one reader per metric, ``e2e/<metric>.py`` for
the end-to-end metrics and ``metrics/<metric>.py`` for the per-layer ones.
Each reader's ``read(run)`` returns a number, or ``None`` when the run holds
nothing for it to read.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile

from bench.loops import clock, loop

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
# A traced run measures a short window: the trace is large and slows the host.
TRACE_SECONDS = 2.0


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def reader(kind: str, name: str):
    path = os.path.join(BENCH, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class Run:
    """What one run measured and checked; what the readers read."""

    def __init__(self, cell: dict, cfg: dict, traffic: dict, seed: int):
        self.cell, self.cfg, self.traffic, self.seed = cell, cfg, traffic, seed
        self.setup_parts: dict[str, float] = {}
        self.window: dict = {}
        self.facts: dict = {}
        self.compared: dict[str, dict] = {}
        self.attempted = self.failed = 0
        self.window_open = None
        self.setup_s = None
        self.compiles_in_window = None
        self.memory_peak_bytes = None
        self.trace = None
        self.peak = None

    def note(self, msg: str) -> None:
        print(f"[bench] {msg}", file=sys.stderr, flush=True)

    def compare(self, name: str, value: float, limits: dict) -> None:
        if name not in limits:
            raise KeyError(f"no limit for compared number {name!r}")
        self.compared[name] = {"value": value, "limit": limits[name]["limit"]}

    @property
    def correct(self) -> bool:
        return (self.failed == 0 and bool(self.compared) and all(
            c["value"] <= c["limit"] for c in self.compared.values()))


class CompileCounter:
    """Counts jaxpr traces and XLA backend compiles while armed."""
    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "traces",
              "/jax/core/compile/backend_compile_duration": "compiles"}

    def __init__(self):
        self.counts = {"traces": 0, "compiles": 0}
        self.armed = False

    def __call__(self, event, duration, **kw):
        if self.armed and event in self.EVENTS:
            self.counts[self.EVENTS[event]] += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        from jax._src import monitoring
        monitoring.unregister_event_duration_listener(self)


def execute(cell, cfg, traffic, limits, seed, seconds, trace, devices, impl,
            t_start, peak=None, controls=(), keep_trace=None):
    """Set up, measure, free the program's state, check.  Returns the
    :class:`Run`.  Device metrics are printed only by :func:`main`, which
    has checked for the chip.

    For the tools only: each of ``controls`` (matmul modes) is checked
    again with the reference at that mode in the program's place, into
    ``run.controls[mode]``; ``keep_trace`` is a directory the raw trace is
    copied to."""
    import jax
    run = Run(cell, cfg, traffic, seed)
    run.peak = peak
    if trace:
        seconds = min(seconds, TRACE_SECONDS)
    drv = loop(traffic["kind"])(cfg, traffic, seed, devices, impl)
    tdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    try:
        with CompileCounter() as counter:
            drv.setup(run, seconds)

            def on_open():
                if trace:
                    jax.profiler.start_trace(tdir)
                counter.armed = True
                run.window_open = clock()

            def on_close():
                counter.armed = False
                if trace:
                    jax.profiler.stop_trace()
            drv.window(run, seconds, on_open, on_close)
            run.compiles_in_window = dict(counter.counts)
        run.setup_s = run.window_open - t_start
        run.note("set-up split: " + " ".join(
            f"{k}={v:.3f}" for k, v in run.setup_parts.items())
            + f" total setup_s={run.setup_s:.3f}")
        run.note("inside the window: jaxpr traces={traces} XLA compiles="
                 "{compiles}".format(**run.compiles_in_window))
        stats = [d.memory_stats() for d in devices]
        if all(stats):
            run.memory_peak_bytes = max(s["peak_bytes_in_use"] for s in stats)
        if trace:
            from bench.trace import Trace
            paths = [os.path.join(dp, f) for dp, _, fs in os.walk(tdir)
                     for f in fs if f.endswith(".xplane.pb")]
            if len(paths) != 1:
                raise RuntimeError(f"expected one trace file, found {paths}")
            if keep_trace:
                shutil.copy(paths[0], keep_trace)
            run.trace = Trace.from_xplane(paths[0])
        drv.release()
        gc.collect()
    finally:
        if tdir:
            shutil.rmtree(tdir, ignore_errors=True)
    drv.check(run, limits)
    run.controls = {}
    for mode in controls:
        ctl = Run(cell, cfg, traffic, seed)
        drv.check(ctl, limits, mode)
        run.controls[mode] = ctl.compared
    return run


def metrics_of(spec: dict, cell_name: str, kind: str, run) -> dict:
    out = {}
    key = "per_layer" if kind == "metrics" else "end_to_end"
    for m in spec[key]:
        if "workloads" in m and cell_name not in m["workloads"]:
            continue
        value = reader(kind, m["name"])(run)
        if value is None:
            print(f"[bench] metric {m['name']}: nothing to read in this run; "
                  "left out", file=sys.stderr, flush=True)
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def parse(argv):
    ap = argparse.ArgumentParser(
        description="Run one benchmark cell once; the last stdout line is "
                    "the JSON result.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def load_cell(name: str):
    spec = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    cfg = load_json(ROOT, cfg_entry["file"])
    traffic = load_json(BENCH, "traffic", f"{cell['traffic']}.json")
    limits = load_json(BENCH, "limits", f"{name}.json")
    return spec, cell, cfg, traffic, limits


def set_compile_cache():
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    # Cache every program, so a second run of a cell compiles nothing.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv, t_start: float) -> int:
    args = parse(argv)
    spec, cell, cfg, traffic, limits = load_cell(args.workload)
    set_compile_cache()
    import jax
    from bench.peaks import peaks
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        print(f"bench: cell {cell['name']} needs {cell['chips']} TPU chip(s); "
              f"JAX found {len(devs)} {devs[0].platform} device(s) "
              f"({devs[0].device_kind})", file=sys.stderr)
        return 2
    devices = devs[:cell["chips"]]
    peak = peaks(devices[0].device_kind)
    run = execute(cell, cfg, traffic, limits, args.seed, args.seconds,
                  bool(args.trace), devices, cfg["impl"], t_start, peak=peak)
    kind = "metrics" if args.trace else "e2e"
    result = {"correct": run.correct, "attempted": run.attempted,
              "failed": run.failed,
              "metrics": metrics_of(spec, cell["name"], kind, run),
              "device": {"platform": devices[0].platform,
                         "kind": devices[0].device_kind,
                         "count": len(devices),
                         "memory_peak_bytes": run.memory_peak_bytes}}
    if args.trace:
        result["device"].update(busy_s=run.trace.busy_s(),
                                window_s=run.trace.window_s)
        result["breakdown"] = {"device_ops": run.trace.top_ops(10),
                               "idle_gaps": run.trace.idle_by_span(10)}
    result["compared"] = run.compared
    for name, c in run.compared.items():
        print(f"compared {name}={c['value']!r} limit={c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
