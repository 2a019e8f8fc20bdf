"""The largest ``peak_bytes_in_use`` over the cell's chips, read after the
window and before the reference runs: set-up included."""


def read(run):
    if run.memory_peak_bytes is None:
        return None
    return run.memory_peak_bytes / 1e9
