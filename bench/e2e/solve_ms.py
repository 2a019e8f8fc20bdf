"""Closed loop: summed wall time of the solves completed in the window over
their count, each solve timed to its ``block_until_ready``."""


def read(run):
    ts = run.window.get("solve_s")
    if not ts:
        return None
    return 1e3 * sum(ts) / len(ts)
