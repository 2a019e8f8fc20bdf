"""Share of the device's busy time in the engine's inner sweep
(``engine.sweep``: the s block solves of each outer step, guards included),
read from the phase scopes of the program's HLO (``bench/phases.py``)."""
from bench.phases import share


def read(run):
    return share(run, "sweep")
