"""Engine phases read back from a compiled program's HLO, for the tracing
tests: the instructions of every loop body (scan, ``lax.map``, cond
branches), and the phase of each one that does work."""
import re
from collections import Counter

from repro.core.hlo_analysis import (COLLECTIVE_KINDS, engine_phase_map,
                                     op_phase)

WORK = {"fusion", "custom-call", "dot", "reduce", *COLLECTIVE_KINDS,
        *(k + "-start" for k in COLLECTIVE_KINDS)}
# The outer scan's own work, the only ops the ``loop`` scope may claim: the
# index block's slice, the history's update slice, the step counter and the
# body call's plumbing -- anything else under it escaped its phase.
LOOP_OPS = re.compile(r"engine\.loop/while/body/"
                      r"(closed_call|dynamic_slice|dynamic_update_slice|add)$")
_HEAD = re.compile(r"^(?:ENTRY )?%([\w.\-]+) .*\{$")
_INST = re.compile(r"^\s+(?:ROOT )?%([\w.\-]+) = .*?\s([a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r'\bop_name="([^"]*)"')


def _computations(text):
    comps, name = {}, None
    for line in text.splitlines():
        head = _HEAD.match(line)
        if head:
            name = head.group(1)
            comps[name] = []
        elif name and _INST.match(line):
            comps[name].append(line)
    return comps


def loop_body_lines(text):
    """Instruction lines of every while body reachable from the entry, and
    of the conditional branches inside them (fused computations and
    reduction regions aside: their fusion or reduce is the instruction)."""
    comps = _computations(text)
    entry = re.search(r"^ENTRY %([\w.\-]+)", text, re.M).group(1)
    calls = re.compile(r"\bbody=%([\w.\-]+)|_computation=%([\w.\-]+)"
                       r"|branch_computations=\{([^}]*)\}")
    todo, seen = [entry], set()
    out = []
    while todo:
        comp = todo.pop()
        if comp in seen:
            continue
        seen.add(comp)
        if comp != entry:
            out += comps[comp]
        for line in comps[comp]:
            for body, branch, branches in calls.findall(line):
                todo += [c for c in (body, branch) if c]
                todo += [b.strip().lstrip("%") for b in branches.split(",")
                         if b.strip()]
    return out


def work_phases(text, *, strict=True):
    """Phase counts of the loop bodies' working instructions, after checking
    that each has exactly one phase: its innermost ``engine.*`` scope, the
    same in every op_name the compiler merged into it.  ``strict=False``
    lets through instructions the compiler made itself, with no source line
    (no ``stack_frame_id``) and no phase (the CPU backend's wrapped
    broadcasts and reduce-windows, its SPMD partitioner's copies): nothing
    in the program's code can name their phase."""
    phases = engine_phase_map(text)
    counts = Counter()
    for line in loop_body_lines(text):
        name, opcode = _INST.match(line).groups()
        if opcode not in WORK or 'custom_call_target="AllocateBuffer"' in line:
            continue            # no work: a buffer, a copy, a get-tuple...
        op_name = _OP_NAME.search(line)
        op_name = op_name and op_name.group(1)
        phase = phases[name]
        if phase is None and not strict and "stack_frame_id=" not in line:
            counts["compiler-made"] += 1
            continue
        assert phase is not None, f"{name} ({opcode}) has no phase: {op_name}"
        merged = {op_phase(part) for part in (op_name or "").split(";")}
        assert merged - {None} <= {phase}, (name, op_name)
        if phase == "loop":
            assert LOOP_OPS.search(op_name or ""), (name, op_name)
        counts[phase] += 1
    return counts
