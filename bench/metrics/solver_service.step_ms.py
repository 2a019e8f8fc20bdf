"""Mean wall time of ``SolverService.step()`` inside the window, from the
benchmark's own span around each call."""


def read(run):
    steps = run.window.get("step_s")
    if not steps:
        return None
    return 1e3 * sum(steps) / len(steps)
