"""Share of the device's busy time in the engine's metrics phase
(``engine.metrics``: the per-iteration objective and residual and the
history they fill), read from the phase scopes of the program's HLO
(``bench/phases.py``)."""
from bench.phases import share


def read(run):
    return share(run, "metrics")
