"""Supervised s-step solves: bounded retry, checkpointed elastic restart.

``solve_supervised`` wraps any registered ``(formulation, backend)`` solver
(the engine registry of ``repro.core.engine``) in a host-side supervision
loop -- the degradation ladder's third rung (DESIGN.md section 7).  The solve
is cut into SEGMENTS of ``ckpt_every`` outer steps; after each segment the
replicated iterate is snapshotted through the existing
:class:`~repro.checkpoint.CheckpointManager` (CRC manifest + atomic rename
for free), and a device loss -- simulated by a ``device_loss``
:class:`~repro.faults.FaultPlan`, raised host-side as
:class:`DeviceLostError` at the segment that contains the injected step --
triggers a bounded-retry restart with exponential backoff: re-plan a 1D mesh
over the survivors (:func:`plan_solver_mesh`), restore the newest
valid snapshot, and resume from its iteration.  Because
``Formulation.pad_shards`` re-pads the LOGICAL operands to any shard count
and the sharded warm start re-derives the device-varying half of the carry
shard-locally, the restarted solve continues on the smaller mesh and
converges to the same answer as the uninterrupted run (tested to 1e-10 in
f64 on even and ragged schedules).

Segment boundaries are multiples of the current ``s``, so the segmented
solve consumes the SAME outer grouping of the index stream as the
uninterrupted solve -- the CA identity is preserved across restarts, and the
only numerical difference is the warm-start re-derivation's rounding.

Guard coupling: every segment runs with the in-scan guard armed by default;
a tripped segment on the sharded backend degrades the REMAINING segments to
``s = 1`` (rung two -- the local backend's engine runs its own in-driver
s=1 tail, see ``engine._degrade_to_s1_tail``).
"""
from __future__ import annotations

import dataclasses
import math
import time

import jax

from repro.checkpoint import CheckpointManager
from repro.core.distributed import make_solver_mesh
from repro.core.engine import _resolve_form, get_solver, sample_blocks


def plan_solver_mesh(n_devices: int, name: str = "shards"):
    """A 1D mesh over the surviving world size, capped at the devices
    actually present.  ``Formulation.pad_shards`` re-pads the logical
    operands to any shard count, so an elastic restart after device loss is
    just this mesh plus a warm start from the newest checkpoint."""
    return make_solver_mesh(max(1, min(n_devices, len(jax.devices()))), name)


class DeviceLostError(RuntimeError):
    """A device (shard) dropped out of the solve.  ``survivors`` is the world
    size after the loss; ``at_iter`` the inner iteration the solve had
    reached when it died."""

    def __init__(self, survivors: int, at_iter: int):
        super().__init__(
            f"device lost at inner iteration {at_iter}; "
            f"{survivors} device(s) surviving")
        self.survivors = survivors
        self.at_iter = at_iter


@dataclasses.dataclass
class SupervisedResult:
    w: jax.Array
    alpha: jax.Array
    metrics: dict       # segments / restarts / guard telemetry (host ints)


def solve_supervised(formulation: str, backend: str, X, y, lam: float, b: int,
                     s: int, iters: int, key=None, *, ckpt_dir: str,
                     idx=None, lam1: float | None = None, ckpt_every: int = 2,
                     max_restarts: int = 3, backoff: float = 0.01,
                     mesh=None, axis: str = "shards", fault=None,
                     guard: bool = True, impl: str | None = None,
                     keep: int = 3) -> SupervisedResult:
    """Run a registered solver under supervision (see module docstring).

    Args:
      formulation, backend: engine-registry key (``"primal"`` / ``"dual"`` /
        ``"proximal"`` x ``"local"`` / ``"sharded"``).
      ckpt_dir: snapshot directory for the CheckpointManager (sync writes --
        a segment is not "done" until its snapshot is committed).
      ckpt_every: snapshot cadence in OUTER steps (see
        ``cost_model.snapshot_cadence`` for the principled pick).
      max_restarts: bound on elastic restarts before the loss is re-raised.
      backoff: base seconds of exponential backoff (``backoff * 2**k``).
      fault: optional :class:`~repro.faults.FaultPlan`.  In-scan kinds ride
        into every segment (``step0`` keeps the global outer numbering
        aligned); ``device_loss`` is intercepted HERE and raised as
        :class:`DeviceLostError` when the solve reaches its outer step.
      mesh: starting mesh for the sharded backend (defaults to all devices).
    """
    form = _resolve_form(formulation)
    d, n = X.shape
    if idx is None:
        idx = sample_blocks(key, form.sample_dim(d, n), b, iters)
    if backend == "sharded" and mesh is None:
        mesh = plan_solver_mesh(len(jax.devices()), axis)
    n_shards = (math.prod(mesh.devices.shape) if mesh is not None else 1)
    solve = get_solver(formulation, backend)
    mgr = CheckpointManager(ckpt_dir, keep=keep, async_save=False)

    x0 = None
    i = 0                   # inner iterations completed
    cur_s = s
    segments = restarts = total_trips = 0
    resumed_from = -1
    loss_pending = fault is not None and fault.kind == "device_loss"
    loss_iter = fault.step * s if loss_pending else -1
    w = alpha = None

    while i < iters:
        seg = min(ckpt_every * cur_s, iters - i)
        try:
            if loss_pending and i <= loss_iter < i + seg:
                loss_pending = False
                survivors = (fault.survivors if fault.survivors is not None
                             else max(1, n_shards // 2))
                raise DeviceLostError(survivors, i)
            w, alpha, trips = _run_segment(
                solve, backend, form, X, y, lam, b, cur_s, seg, idx[i:i + seg],
                i // cur_s, x0, mesh=mesh, axis=axis, fault=fault,
                guard=guard, impl=impl, lam1=lam1)
        except DeviceLostError as e:
            restarts += 1
            if restarts > max_restarts:
                raise
            time.sleep(backoff * 2 ** (restarts - 1))
            if backend == "sharded":
                n_shards = max(1, e.survivors)
                mesh = plan_solver_mesh(n_shards, axis)
            restored = mgr.restore_latest(like={"x0": jax.ShapeDtypeStruct(
                x0.shape, x0.dtype)} if x0 is not None else None)
            if restored is not None:
                state, extra, _ = restored
                x0 = jax.numpy.asarray(state["x0"])
                i = int(extra["iters_done"])
                cur_s = int(extra["cur_s"])
                resumed_from = i
            else:           # no snapshot yet: cold restart from iteration 0
                x0, i, resumed_from = None, 0, 0
            continue
        segments += 1
        i += seg
        total_trips += trips
        x0 = w if form.operand_layout == "rows" else alpha
        if trips and cur_s > 1 and backend == "sharded":
            cur_s = 1       # rung two for the sharded backend (host-side)
        mgr.save(i, {"x0": x0}, extra={"iters_done": i, "cur_s": cur_s},
                 block=True)
    mgr.close()
    return SupervisedResult(w, alpha, {
        "segments": segments, "restarts": restarts,
        "guard_trips": total_trips, "resumed_from_iter": resumed_from,
        "final_n_shards": n_shards, "final_s": cur_s})


def _run_segment(solve, backend, form, X, y, lam, b, cur_s, seg, seg_idx,
                 step0, x0, *, mesh, axis, fault, guard, impl, lam1):
    """One supervised segment through the registry solver; returns
    ``(w, alpha, trips)`` with ``trips`` a host int."""
    kw = {"idx": seg_idx, "guard": guard, "fault": fault, "step0": step0,
          "impl": impl}
    if lam1 is not None:
        kw["lam1"] = lam1
    if backend == "local":
        if x0 is not None:
            kw["w0" if form.operand_layout == "rows" else "alpha0"] = x0
        res = solve(X, y, lam, b, cur_s, seg, None, **kw)
        trips = (int(jax.device_get(res.metrics["guard_trips"]))
                 if guard else 0)
        return res.w, res.alpha, trips
    out = solve(mesh, X, y, lam, b, cur_s, seg, None, axis=axis, x0=x0, **kw)
    if guard:
        w, alpha, m = out
        return w, alpha, int(jax.device_get(m["guard_trips"]))
    w, alpha = out
    return w, alpha, 0
