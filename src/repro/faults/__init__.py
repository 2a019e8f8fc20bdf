"""Fault injection + supervised solves for the s-step engine (DESIGN.md
section 7).  ``FaultPlan`` is import-light (tests thread it into every
solver); the supervisor pulls in the checkpoint stack lazily."""
from .plan import KINDS, FaultPlan

__all__ = ["FaultPlan", "KINDS", "DeviceLostError", "SupervisedResult",
           "solve_supervised"]


def __getattr__(name):
    if name in ("DeviceLostError", "SupervisedResult", "solve_supervised"):
        from . import supervisor
        return getattr(supervisor, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
