"""Run one benchmark cell once, from the root of a checkout:

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cells are the ``workloads`` of ``BENCHMARK.json``.  The last line of
standard output is the JSON result; ``--trace 1`` reports the per-layer
metrics from a profiler trace of a short window instead of the end-to-end
ones.  Without the TPU chips the cell asks for it prints no result and
exits with code 2.
"""
import os
import sys
import time

if __name__ == "__main__":
    T_START = time.perf_counter()
    ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness
    sys.exit(harness.main(sys.argv[1:], T_START))
