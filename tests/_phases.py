"""Engine phases read back from a compiled program's HLO, for the tracing
tests: the instructions of every loop body (scan, ``lax.map``, cond
branches), the phase of each one that does work, and where a phase reads
an operand-sized array."""
import math
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


def _reachable(text):
    """``(computation, depth, line)`` for every instruction line of the
    entry (depth 0), of every while body reachable from it (one deeper than
    the loop that runs it) and of the conditional branches inside them (the
    depth of their conditional).  Fused computations and reduction regions
    aside: their fusion or reduce is the instruction."""
    comps = _computations(text)
    entry = re.search(r"^ENTRY %([\w.\-]+)", text, re.M).group(1)
    calls = re.compile(r"\bbody=%([\w.\-]+)|_computation=%([\w.\-]+)"
                       r"|branch_computations=\{([^}]*)\}")
    todo, seen = [(entry, 0)], set()
    while todo:
        comp, depth = todo.pop()
        if comp in seen:
            continue
        seen.add(comp)
        for line in comps[comp]:
            yield comp, depth, line
            for body, branch, branches in calls.findall(line):
                if body:
                    todo.append((body, depth + 1))
                todo += [(b.strip().lstrip("%"), depth)
                         for b in [branch, *branches.split(",")] if b.strip()]


def loop_body_lines(text):
    """Instruction lines of every while body reachable from the entry, and
    of the conditional branches inside them."""
    entry = re.search(r"^ENTRY %([\w.\-]+)", text, re.M).group(1)
    return [line for comp, _, line in _reachable(text) if comp != entry]


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


_SHAPE = re.compile(r"^\s+(?:ROOT )?%([\w.\-]+) = [a-z]\w*\[([\d,]*)\]")
_ARGS = re.compile(r"\s[a-z][a-z0-9\-]*\(([^)]*)\)")


def _numel(text):
    """``{instruction name: element count}`` of every array-valued one."""
    out = {}
    for line in text.splitlines():
        m = _SHAPE.match(line)
        if m:
            out[m.group(1)] = math.prod(int(x) for x in m.group(2).split(",")
                                        if x)
    return out


def operand_reads(text, numel, phase):
    """``{(depth, computation): count}`` of the working instructions in
    ``phase`` that take an argument of at least ``numel`` elements -- a
    pass over the operand.  Depth 0 is the entry, 1 the outer scan's body,
    2 a loop inside it (``_reachable``)."""
    sizes = _numel(text)
    phases = engine_phase_map(text)
    out = Counter()
    for comp, depth, line in _reachable(text):
        name, opcode = _INST.match(line).groups()
        if opcode not in WORK or phases.get(name) != phase:
            continue
        args = _ARGS.search(line[line.index(" = "):]).group(1)
        if any(sizes.get(a.strip().lstrip("%"), 0) >= numel
               for a in args.split(",")):
            out[depth, comp] += 1
    return out
