"""The engine's phases in a traced window: each device op labelled with the
``engine.<phase>`` scope the program traced it under, and the device seconds
per phase.

A trace names its device ops by HLO instruction; the phase of an instruction
is in its ``op_name`` metadata, which the trace does not carry.  So the
program the window ran is rebuilt: the cell's own entry
(``closed_loop.Loop._entry``) lowered on abstract arguments with the
window's shapes, dtypes and shardings, then compiled (the persistent cache
holds it from set-up).  One lowering compiles to the same instruction names
each time, and ``repro.core.hlo_analysis.engine_phase_map`` reads the phases
from its HLO text.

The lookup refuses to guess: when any op of the window (containers aside)
is not an instruction of the rebuilt program, no phase is reported.
"""
from __future__ import annotations

import time
from collections import defaultdict

from bench.trace import CONTAINER, merge

UNSCOPED = "unscoped"


def program_text(cfg: dict, traffic: dict, devices, impl: str) -> str:
    """HLO text of the solve a closed-loop cell compiles in set-up, rebuilt
    from shapes alone."""
    import jax
    from jax.sharding import SingleDeviceSharding

    from bench import datagen
    from bench.loops import operand_class
    from bench.loops.closed_loop import Loop
    loop = Loop(cfg, traffic, 0, devices, impl)
    gen, gen_idx, mesh = loop._generators()
    key = jax.eval_shape(lambda: datagen.subkey(0, "data"))
    X, y = jax.eval_shape(gen, key)
    idx = jax.eval_shape(gen_idx, key)
    if mesh is None:
        one = SingleDeviceSharding(devices[0])
        X = jax.eval_shape(operand_class(cfg["operand"]).of, X)
        X, y, idx = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
            (X, y, idx))
    return jax.jit(loop._entry(mesh)).lower(X, y, idx).compile().as_text()


def op_key(trace_name: str) -> str:
    """The instruction name of a trace op: ``custom-call.40 [Cholesky]``
    is instruction ``custom-call.40``."""
    return trace_name.split(" [", 1)[0]


def attribute(trace, phases: dict, note=None) -> dict | None:
    """Device seconds (per device, in the window) by phase, the ops no
    scope covers under :data:`UNSCOPED`; ``None`` when a traced op of the
    window is missing from ``phases``, and ``note`` is told which."""
    per: dict[tuple, list] = defaultdict(list)
    missing = set()
    for dev in trace.devices:
        for name, s, e in trace.ops[dev] + trace.async_ops.get(dev, []):
            if (min(e, trace.hi) > max(s, trace.lo)
                    and not CONTAINER.search(name)
                    and op_key(name) not in phases):
                missing.add(name)
        for name, s, e in trace.ops[dev]:
            if not CONTAINER.search(name) and op_key(name) in phases:
                per[(dev, phases[op_key(name)] or UNSCOPED)].append((s, e))
    if missing:
        if note:
            note(f"engine phases: {len(missing)} traced op(s) are not in the "
                 f"rebuilt program, so no phase is read: "
                 f"{sorted(missing)[:10]}")
        return None
    out: dict[str, float] = defaultdict(float)
    for (_, phase), ivs in per.items():
        out[phase] += sum(e - s for s, e in merge(ivs, trace.lo, trace.hi)
                          ) * 1e-9 / len(trace.devices)
    return dict(out)


def top_ops(trace, phases: dict, k: int = 3) -> dict[str, list]:
    """The ``k`` ops with the most device seconds in each phase."""
    tot: dict[str, dict] = defaultdict(lambda: defaultdict(float))
    for name, secs in trace.top_ops(10 ** 9):
        tot[phases.get(op_key(name)) or UNSCOPED][name] += secs
    return {ph: sorted(ops.items(), key=lambda kv: -kv[1])[:k]
            for ph, ops in tot.items()}


def phase_seconds(run) -> dict | None:
    """Device seconds by phase in a traced run of a closed-loop cell,
    computed once per run; ``None`` in an untraced run, in another kind of
    cell, for a program that names no phases, or when the rebuilt program
    misses a traced op."""
    if run.trace is None or run.traffic.get("kind") != "closed_loop":
        return None
    if "engine_phases" not in run.facts:
        run.facts["engine_phases"] = _measure(run)
    return run.facts["engine_phases"]


def _measure(run) -> dict | None:
    try:
        from repro.core.hlo_analysis import engine_phase_map
    except ImportError:
        run.note("engine phases: this program has no phase reader")
        return None
    import jax
    t0 = time.perf_counter()
    phases = engine_phase_map(program_text(
        run.cfg, run.traffic, jax.devices()[:run.cell["chips"]],
        run.cfg["impl"]))
    rebuild_s = time.perf_counter() - t0
    if not any(phases.values()):
        run.note("engine phases: the program names no phase")
        return None
    secs = attribute(run.trace, phases, run.note)
    if secs is None:
        return None
    busy = run.trace.busy_s()
    run.note("engine phases, device s per chip in the window (busy "
             f"{busy:.4f} s): " + " ".join(
                 f"{ph}={v:.4f}" for ph, v in sorted(secs.items()))
             + f"; {UNSCOPED} {100 * secs.get(UNSCOPED, 0.0) / busy:.2f}% "
             f"of busy; program rebuilt in {rebuild_s:.2f} s")
    for ph, ops in sorted(top_ops(run.trace, phases).items()):
        run.note(f"engine phase {ph}, largest ops: " + ", ".join(
            f"{n} {v:.4f} s" for n, v in ops))
    return secs


def share(run, phase: str) -> float | None:
    """Device time of ``phase`` over device busy time, in %."""
    secs = phase_seconds(run)
    if secs is None:
        return None
    return 100.0 * secs.get(phase, 0.0) / run.trace.busy_s()
