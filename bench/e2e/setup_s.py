"""Seconds from process start until the measured window opens: data, the
operand build, compiles (from the persistent cache when warm), warm-up."""


def read(run):
    return run.setup_s
