"""Roofline share of the Gram-packet all-reduce of a sharded solve: the
least time the window's packet all-reduces could take over the chip's
interconnect (``work/allreduce.py``, ``ici_bits_per_s`` of ``peaks.json``)
over their device time, per chip.

A trace names its ops by HLO instruction, and an all-reduce is named after
the program's operation (``psum_invariant.13``), not after its opcode; so
the opcodes are read from the program the window ran, rebuilt from shapes
(``bench/phases.py``).  An asynchronous all-reduce is timed from its start
op's start to its done op's end, never by the done alone, which is only the
wait.  An all-reduce counts when it lies wholly inside the window."""
import re

from bench.work import allreduce

# ``%name = <type> all-reduce[-start|-done](%first_operand, ...``
INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.-]+)\s*=.*?\s"
                   r"(all-reduce(?:-start|-done)?)\((?:%([\w.-]+))?", re.M)


def all_reduce_ops(hlo_text: str) -> dict:
    """``{instruction: "sync" | "start" | ("done", its start)}``."""
    ops = {}
    for name, opcode, operand in INSTR.findall(hlo_text):
        if opcode == "all-reduce-done":
            ops[name] = ("done", operand)
        else:
            ops[name] = "start" if opcode.endswith("start") else "sync"
    return ops


def occurrences(trace, ops: dict) -> dict:
    """Per device, the ``(start, end)`` of each all-reduce wholly inside
    the window: a synchronous op's own interval, or an asynchronous one's
    from the earliest event of its start op to the end of its done op."""
    out = {}
    for dev in trace.devices:
        open_at, got = {}, []
        mine = [(name.split(" [", 1)[0], s, e) for name, s, e in
                trace.ops[dev] + trace.async_ops.get(dev, [])
                if name.split(" [", 1)[0] in ops]
        for key, s, e in sorted(mine, key=lambda ev: ev[1]):
            kind = ops[key]
            if kind == "sync":
                got.append((s, e))
            elif kind == "start":
                open_at.setdefault(key, s)
            else:
                got.append((open_at.pop(kind[1], s), e))
        out[dev] = [(s, e) for s, e in got if s >= trace.lo and e <= trace.hi]
    return out


def _ops(run) -> dict:
    if "wire_ops" not in run.facts:
        import jax

        from bench.phases import program_text
        run.facts["wire_ops"] = all_reduce_ops(program_text(
            run.cfg, run.traffic, jax.devices()[:run.cell["chips"]],
            run.cfg["impl"]))
    return run.facts["wire_ops"]


def read(run):
    shards = run.traffic.get("shards", 1)
    if (run.trace is None or shards < 2 or not run.facts.get("packet")
            or run.traffic.get("kind") != "closed_loop"):
        return None
    occ = occurrences(run.trace, _ops(run))
    count = sum(len(v) for v in occ.values())
    if not count:
        return None
    rows = run.facts["packet"][0]
    least = allreduce.least_s(rows * (rows + 1), shards, run.facts["itemsize"],
                              run.peak)
    busy = sum(e - s for v in occ.values() for s, e in v) * 1e-9
    return 100.0 * count * least / busy
