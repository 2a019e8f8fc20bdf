"""Share of the device's busy time in the engine's bind phase
(``engine.bind``: the operand's layout and the first iterate inside the
compiled solve; in the sharded solve, each chip's relayout of its column
shard into row slabs, once a call), read from the phase scopes of the
program's HLO (``bench/phases.py``)."""
from bench.phases import share


def read(run):
    return share(run, "bind")
