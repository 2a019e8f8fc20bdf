"""The chip's published peaks, from ``peaks.json``, keyed by the
``device_kind`` JAX reports.  A device that is not in the table is an
error: a roofline against a guessed peak is no measurement."""
from __future__ import annotations

import json
import os

PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind: str, path: str = PATH) -> dict:
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in {os.path.basename(path)} (known: {sorted(table)})")
    return table[device_kind]


def roofline_s(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take for this work."""
    return max(flops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
