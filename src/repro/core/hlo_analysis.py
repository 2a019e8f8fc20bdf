"""HLO op analysis: the one parser behind every ``compiled.as_text()`` reader.

This is the measurement backbone for (a) the paper's latency claim -- the
number of collectives on the critical path drops by exactly ``s`` in CA-BCD /
CA-BDCD, which we verify by counting ops in compiled HLO -- and (b) the
roofline collective term, which ``cost_analysis()`` does not report, so we
parse ``compiled.as_text()`` and sum operand sizes of every all-gather /
all-reduce / reduce-scatter / all-to-all / collective-permute op.  The static
contract engine (``repro.analysis``) builds its HLO pass on the same parser:
:func:`parse_named_ops` generalizes the line scan to arbitrary opcodes
(transpose, gather, fusion) so the PR-5 "no dual pre-transpose" and PR-2
"panel never materializes" guarantees are checked from one source of truth.
:func:`engine_phase_map` reads the engine's phase scopes back from each
instruction's ``op_name``, to label the ops of a device profile.

Conventions, verified against JAX 0.4.37 CPU-backend HLO (fixture snapshots
of that output live in ``tests/fixtures/hlo/`` so the parser is unit-tested
without a live compile) and exercised on every JAX 0.9.0 lowering the
analysis sweep parses:

  %name = f32[8,9]{1,0} all-reduce(f32[8,9]{1,0} %op), channel_id=1,
      replica_groups={{0,1,2,3,4,5,6,7}}, use_global_device_ids=true, ...

* ``replica_groups`` appears in BOTH forms on 0.4.37: the brace form
  ``{{0,1,...}}`` (shard_map/GSPMD output, group size = ids per group) and
  the iota form ``[2,4]<=[8]`` (group size = second bracket entry).
* Async collectives split into ``-start``/``-done`` pairs; the ``-start``
  result is the tuple ``(operand-shape(s), result-shape(s))``, so its summed
  byte size is halved and the ``-done`` line is skipped -- each logical
  collective is counted exactly once.
* Result-shape bytes are parsed from the type; operand bytes are derived per
  op kind (all-gather results are group_size x the operand, reduce-scatter
  the inverse).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Iterable

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "c64": 8, "c128": 16,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "s4": 0.5, "u4": 0.5, "pred": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1, "f8e4m3fnuz": 1, "f8e5m2fnuz": 1,
}

COLLECTIVE_KINDS = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-broadcast", "ragged-all-to-all",
)

_OP_RE = re.compile(
    r"=\s*(?P<type>\([^=]*?\)|[a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?)\s*"
    r"(?P<kind>" + "|".join(COLLECTIVE_KINDS) + r")(?P<phase>-start|-done)?\(")
_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
_GROUPS_BRACKET_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_BRACE_RE = re.compile(r"replica_groups=\{\{([0-9, ]*)\}")


@dataclasses.dataclass(frozen=True)
class CollectiveOp:
    kind: str
    result_bytes: float   # bytes of the op's result shape(s)
    operand_bytes: float  # derived operand bytes ("words on the wire" source)
    link_bytes: float     # ring-model bytes crossing links per device
    group_size: int
    line: str


def _shape_bytes(type_str: str) -> float:
    total = 0.0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        size = _DTYPE_BYTES.get(dtype)
        if size is None:
            continue  # token[...] that is not a dtype (e.g. sharding annotations)
        n = 1
        if dims:
            for piece in dims.split(","):
                n *= int(piece)
        total += n * size
    return total


def _group_size(line: str, default: int = 1) -> int:
    m = _GROUPS_BRACKET_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_BRACE_RE.search(line)
    if m:
        ids = [p for p in m.group(1).replace(" ", "").split(",") if p]
        return max(len(ids), 1)
    return default


def parse_collectives(hlo_text: str, total_devices: int | None = None) -> list[CollectiveOp]:
    ops: list[CollectiveOp] = []
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        if m.group("phase") == "-done":
            continue  # paired with a counted -start
        kind = m.group("kind")
        type_str = m.group("type")
        result = _shape_bytes(type_str)
        if m.group("phase") == "-start" and type_str.startswith("("):
            # -start result is (operand(s), result(s)); halve to avoid double count.
            result /= 2
        g = _group_size(line, default=total_devices or 1)
        if kind == "all-gather":
            operand = result / max(g, 1)
            link = result * (g - 1) / max(g, 1)
        elif kind == "reduce-scatter":
            operand = result * g
            link = operand * (g - 1) / max(g, 1)
        elif kind == "all-reduce":
            operand = result
            link = 2 * result * (g - 1) / max(g, 1)
        elif kind in ("all-to-all", "ragged-all-to-all"):
            operand = result
            link = result * (g - 1) / max(g, 1)
        else:  # collective-permute / broadcast
            operand = result
            link = result
        ops.append(CollectiveOp(kind, result, operand, link, g, line.strip()[:200]))
    return ops


@dataclasses.dataclass(frozen=True)
class CollectiveSummary:
    count: int
    operand_bytes: float
    link_bytes: float
    by_kind: dict

    def __str__(self) -> str:
        parts = [f"{k}: n={v[0]} operand={v[1]:.3e}B link={v[2]:.3e}B"
                 for k, v in sorted(self.by_kind.items())]
        return (f"collectives total n={self.count} operand={self.operand_bytes:.3e}B "
                f"link={self.link_bytes:.3e}B | " + "; ".join(parts))


def summarize(ops: Iterable[CollectiveOp]) -> CollectiveSummary:
    by_kind: dict[str, list] = {}
    count = 0
    ob = lb = 0.0
    for op in ops:
        count += 1
        ob += op.operand_bytes
        lb += op.link_bytes
        ent = by_kind.setdefault(op.kind, [0, 0.0, 0.0])
        ent[0] += 1
        ent[1] += op.operand_bytes
        ent[2] += op.link_bytes
    return CollectiveSummary(count, ob, lb, {k: tuple(v) for k, v in by_kind.items()})


def collective_summary(hlo_text: str, total_devices: int | None = None) -> CollectiveSummary:
    return summarize(parse_collectives(hlo_text, total_devices))


def count_in_compiled(compiled) -> CollectiveSummary:
    """Summary for a jax ``Compiled`` object."""
    return collective_summary(compiled.as_text())


# ---------------------------------------------------------------------------
# Generic named-op scan -- the contract engine's view of the HLO text.
# ---------------------------------------------------------------------------

# An HLO instruction line:  %name = TYPE opcode(OPERANDS), attrs...
# TYPE is either a tuple "(f32[..], ...)" or "dtype[dims]{layout}".
_NAMED_OP_RE = re.compile(
    r"(?P<result>%[\w.\-]+)\s*=\s*"
    r"(?P<type>\([^=]*?\)|[a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?)\s*"
    r"(?P<opcode>[a-z][a-z0-9\-]*)\(")
_OP_NAME_RE = re.compile(r'\bop_name="([^"]*)"')


@dataclasses.dataclass(frozen=True)
class HloOp:
    """One parsed HLO instruction: opcode, result shapes, raw line (cut at
    200 characters) and the ``op_name`` of its metadata (``None`` when the
    compiler left the instruction without one)."""
    opcode: str
    result_name: str
    # ((dtype, (dims...)), ...): every dtype[...] in the result type -- one
    # entry for plain results, several for tuple-shaped (-start) results.
    result_shapes: tuple
    line: str
    op_name: str | None = None

    def shapes(self) -> tuple:
        """Just the dim tuples, dtype dropped."""
        return tuple(dims for _, dims in self.result_shapes)

    def dtypes(self) -> tuple:
        return tuple(dt for dt, _ in self.result_shapes)


def _parse_shapes(type_str: str) -> tuple:
    out = []
    for dtype, dims in _SHAPE_RE.findall(type_str):
        if dtype not in _DTYPE_BYTES:
            continue  # not a dtype token (layout/sharding noise)
        out.append((dtype, tuple(int(p) for p in dims.split(",")) if dims else ()))
    return tuple(out)


def parse_named_ops(hlo_text: str, opcodes: Iterable[str] | None = None) -> list[HloOp]:
    """Scan HLO text for instruction lines, optionally filtered by opcode.

    The contract engine uses this for the non-collective checks: ``transpose``
    ops whose result is operand-shaped (the legacy dual pre-transpose),
    ``gather``/``fusion`` ops whose result is a materialized (sb, n_local)
    panel, and dtype inspection of the collectives for the f64 packet check.
    Operand shapes inside the parens are deliberately NOT parsed -- result
    shapes are enough to identify every contract violation by shape, and the
    operand syntax varies more across JAX versions.
    """
    wanted = set(opcodes) if opcodes is not None else None
    ops: list[HloOp] = []
    for line in hlo_text.splitlines():
        m = _NAMED_OP_RE.search(line)
        if not m:
            continue
        opcode = m.group("opcode")
        if wanted is not None and opcode not in wanted:
            continue
        name = _OP_NAME_RE.search(line)
        ops.append(HloOp(opcode, m.group("result"),
                         _parse_shapes(m.group("type")), line.strip()[:200],
                         name.group(1) if name else None))
    return ops


def collective_dtypes(hlo_text: str) -> set:
    """Dtypes carried by every counted collective (``-done`` lines skipped).

    Backs the f64-packet contract: under the x64 test path every packet
    reduction must accumulate in f64, so this set must be ``{"f64"}``.
    """
    dts: set[str] = set()
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m or m.group("phase") == "-done":
            continue
        for dt, _ in _parse_shapes(m.group("type")):
            dts.add(dt)
    return dts


# ---------------------------------------------------------------------------
# Engine phases -- the named scopes core/engine.py opens around each phase of
# the s-step loop, read back from every instruction's ``op_name``.
# ---------------------------------------------------------------------------

PHASE_PREFIX = "engine."
ENGINE_PHASES = ("bind", "loop", "packet", "reduce", "assemble", "sweep",
                 "update", "metrics")
# A scope is one component of the name stack: ``.../engine.sweep/...``, or
# wrapped by a transform, ``vmap(engine.packet)``; ``;`` joins the names of
# instructions the compiler merged.
_PHASE_RE = re.compile(r"(?:^|[/(;])engine\.([a-z]+)(?=$|[/);])")
_COMPUTATION_RE = re.compile(r"^(?:ENTRY )?%([\w.\-]+) .*\{$")
_CALLS_RE = re.compile(r"\bcalls=%([\w.\-]+)")


def op_phase(op_name: str | None) -> str | None:
    """The innermost ``engine.<phase>`` scope of an ``op_name``, or
    ``None``.  Innermost wins: the pipelined driver contracts the next
    step's packet inside the ring reduction, and that contraction is
    ``packet`` work, not ``reduce``."""
    found = _PHASE_RE.findall(op_name or "")
    return found[-1] if found else None


def engine_phase_map(hlo_text: str) -> dict[str, str | None]:
    """``{instruction name: engine phase or None}`` for every instruction of
    a compiled program's HLO text (``compiled.as_text()``), names without
    the ``%``.  A profiler trace names its device ops by these instruction
    names, so this map labels each op of a profile with its phase.

    A fusion the compiler built without any ``op_name`` takes the phase of
    the instructions it fuses when they name exactly one.  Constants count
    only when nothing else names a phase: XLA merges equal constants, and
    the one it keeps names whichever op made it first."""
    phases: dict[str, str | None] = {}
    inside: dict[str, tuple[set, set]] = {}     # (ops, constants)
    calls: dict[str, str] = {}
    comp = None
    for line in hlo_text.splitlines():
        head = _COMPUTATION_RE.match(line)
        if head:
            comp = head.group(1)
            continue
        m = _NAMED_OP_RE.search(line)
        if not m:
            continue
        name = m.group("result").lstrip("%")
        op_name = _OP_NAME_RE.search(line)
        phases[name] = op_phase(op_name and op_name.group(1))
        ops, consts = inside.setdefault(comp, (set(), set()))
        (consts if m.group("opcode") == "constant" else ops).add(phases[name])
        called = _CALLS_RE.search(line)
        if op_name is None and called:
            calls[name] = called.group(1)
    for name, comp in calls.items():
        ops, consts = inside.get(comp, (set(), set()))
        found = (ops - {None}) or (consts - {None})
        if len(found) == 1:
            phases[name] = found.pop()
    return phases
