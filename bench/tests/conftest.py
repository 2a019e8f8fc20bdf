"""Shared set-up of the benchmark's own tests: each cell cut to a size the
CPU runs in seconds, with the Pallas kernels in interpret mode."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# Tiny shapes: (d, n) per configuration; s = 4 keeps interpret mode fast.
TINY = {"realsim-primal": (64, 1024), "news20-dual": (1024, 64)}


# Cells whose files are kept under bench/ but that are not (yet) entries of
# BENCHMARK.json; they are rehearsed all the same.
KEPT_OUT = {
    "realsim-primal.serve": {"config": "realsim-primal",
                             "traffic": "open-poisson", "chips": 1},
    "realsim-primal.solve-4chip": {"config": "realsim-primal",
                                   "traffic": "closed-1280-4shard",
                                   "chips": 4},
}


def load_cell(name: str):
    from bench import harness
    if name not in KEPT_OUT:
        return harness.load_cell(name)
    cell = dict(KEPT_OUT[name], name=name)
    return (harness.load_json(harness.ROOT, "BENCHMARK.json"), cell,
            harness.load_json(harness.BENCH, "configs",
                              f"{cell['config']}.json"),
            harness.load_json(harness.BENCH, "traffic",
                              f"{cell['traffic']}.json"),
            harness.load_json(harness.BENCH, "limits", f"{name}.json"))


def tiny_cell(name: str):
    spec, cell, cfg, traffic, limits = load_cell(name)
    d, n = TINY[cell["config"]]
    cfg = {**cfg, "d": d, "n": n - (name.endswith("4chip") * 2), "s": 4}
    traffic = dict(traffic)
    if traffic["kind"] == "closed_loop":
        traffic.update(iters=64, streams=4)
    else:
        traffic.update(rate_per_s=20.0, preroll_s=0.5, drain_s=60.0, warm_to=16,
                       check_tickets=8,
                       service=dict(traffic["service"], slots=16,
                                    min_bucket=4, chunk_iters=8,
                                    max_iters=32))
    return spec, cell, cfg, traffic, limits


def run_tiny(name: str, seed: int = 5 + 2 ** 33, seconds: float = 1.0,
             controls=()):
    import time

    import jax
    from bench import harness
    spec, cell, cfg, traffic, limits = tiny_cell(name)
    devs = jax.devices()[:cell["chips"]]
    return harness.execute(cell, cfg, traffic, limits, seed, seconds, False,
                           devs, "pallas_interpret", time.perf_counter(),
                           controls=controls)


@pytest.fixture
def cpu_only():
    import jax
    if jax.devices()[0].platform != "cpu":
        pytest.skip("CPU rehearsal")
