"""Traffic loops, one module per traffic ``kind``: ``closed_loop.py``
(one solve after another) and ``open_loop.py`` (the solve service under
scheduled arrivals).  A traffic file names its kind and gives the
parameters; the loop named by the kind reads them.

A loop is built from ``(cfg, traffic, seed, devices, impl)`` and offers
``setup(run)``, ``window(run, seconds, tracing)``, ``release()`` and
``check(run, limits, mode)``; shared helpers live here.
"""
from __future__ import annotations

import contextlib
import importlib
import time

import numpy as np


def loop(kind: str):
    return importlib.import_module(f"bench.loops.{kind}").Loop


def clock() -> float:
    return time.perf_counter()


def span(name: str):
    """A benchmark host span, written into the profiler's trace when one is
    being recorded (``bench.*`` names are what the trace reduction reads)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


@contextlib.contextmanager
def timed(parts: dict, key: str):
    t0 = clock()
    yield
    parts[key] = parts.get(key, 0.0) + clock() - t0


def rel(a, b) -> float:
    """||a - b|| / ||b|| in float64 on the host."""
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-300))


def operand_class(name: str):
    from repro.kernels.gram import ColMajorOperand, RowMajorOperand
    return {"rows": RowMajorOperand, "cols": ColMajorOperand}[name]


def sample_dim(cfg: dict) -> int:
    return cfg["d"] if cfg["formulation"] == "primal" else cfg["n"]


def reference_fn(formulation: str, mode: str):
    """The jitted plain reference ``(X, y, lam, idx) -> (w, alpha)``."""
    import jax
    from bench.reference import matmul, module
    mod, mm = module(formulation), matmul(mode)
    return jax.jit(lambda X, y, lam, idx: mod.solve(X, y, lam, idx, mm))
