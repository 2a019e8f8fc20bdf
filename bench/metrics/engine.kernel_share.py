"""Share of the device's busy time spent in the solver's Pallas kernels;
the rest is the XLA ops of the inner sweep, assembly, scatter and metrics."""
PATTERN = r"\[tpu_custom_call\]$"


def read(run):
    if run.trace is None or not run.trace.matching(PATTERN):
        return None
    return 100.0 * run.trace.time_s(PATTERN) / run.trace.busy_s()
