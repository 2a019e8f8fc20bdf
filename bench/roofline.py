"""A kernel's roofline share: the least time its calls could take on the
chip (the algorithm's work per call from ``work/``, the peaks from
``peaks.json``) over the device time of its events in the trace."""
from __future__ import annotations

from bench.peaks import roofline_s


def share(run, pattern: str, fact: str, work) -> float | None:
    """Percent, or ``None`` when the run has no trace, no such kernel call
    on its path (``run.facts[fact]``), or no event matching ``pattern``."""
    if run.trace is None or not run.facts.get(fact):
        return None
    events = run.trace.matching(pattern)
    if not events:
        return None
    rows, contraction = run.facts[fact]
    flops, nbytes = work(rows, contraction, run.facts["itemsize"])
    busy = sum(e - s for _, _, s, e in events) * 1e-9
    return 100.0 * len(events) * roofline_s(flops, nbytes, run.peak) / busy
