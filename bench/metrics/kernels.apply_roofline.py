"""Roofline share of the panel-apply kernel (alpha += Y^T dw primal,
w -= Y da dual): least time for the applies the algorithm needs over their
device time."""
from bench.roofline import share
from bench.work import apply

PATTERN = r"^panel_apply(_cols)?_pallas\b"


def read(run):
    return share(run, PATTERN, "apply", apply.work)
