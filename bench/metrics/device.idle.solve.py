"""Idle share of the chip in the traced window of a solve cell: 1 minus the
union of device op intervals over the window, averaged over the chips."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * run.trace.idle_share()
