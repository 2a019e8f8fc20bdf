"""The reduction from a profiler trace to numbers: device busy time as the
union of op intervals, idle share, op time by name pattern, and idle gaps
attributed to what the benchmark's own host spans were doing.

A :class:`Trace` holds only what the reduction needs, per device the op
intervals ``(name, start_ns, end_ns)`` and the benchmark's host spans, all
on one clock, and clips everything to the ``bench.window`` span.  It is
read from the ``.xplane.pb`` the JAX profiler writes, and round-trips
through JSON so the reduction is tested on a recorded trace.
"""
from __future__ import annotations

import bisect
import json
import re
from collections import defaultdict

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
# Control-flow ops enclose the ops of their bodies on the same trace line
# (a solve's outer scan is one ``while`` event as long as the solve); busy
# time and the op table count the ops that do the work.
CONTAINER = re.compile(r"^(while|conditional|call)(\.\d+)?( |$)")


def op_name(text: str) -> str:
    """The HLO instruction name of a device op event, whose name is the
    instruction's text (``%name = type op(...), ...``), with the custom
    call's target appended: ``custom-call.40 [Cholesky]``.  A Pallas
    kernel's instruction is named after its jitted wrapper, as
    ``panel_apply_pallas.5 [tpu_custom_call]``."""
    if " = " not in text:
        return text
    name = text.split(" = ", 1)[0].lstrip("%")
    m = re.search(r'custom_call_target="([^"]+)"', text)
    return f"{name} [{m.group(1)}]" if m else name


def merge(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Union of ``(start, end)`` intervals, clipped to ``[lo, hi]``."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


class Trace:
    def __init__(self, ops: dict, spans: list, window: tuple[float, float],
                 async_ops: dict | None = None):
        self.ops = {dev: [tuple(o) for o in evs] for dev, evs in ops.items()}
        # Ops the chip runs asynchronously (the "Async XLA Ops" line: an
        # all-reduce or a copy in flight) are found by name, but they do not
        # count as busy: compute may be idle while they fly.
        self.async_ops = {dev: [tuple(o) for o in evs]
                          for dev, evs in (async_ops or {}).items()}
        self.spans = [tuple(s) for s in spans]
        self.lo, self.hi = window
        # The benchmark's spans inside the window follow one another (the
        # host loop opens one at a time), so one lookup by start finds the
        # span open at any instant.
        inner = sorted((s, e, n) for n, s, e in self.spans if n != WINDOW_SPAN)
        self._starts = [s for s, _, _ in inner]
        self._inner = inner

    # ------------------------------------------------------------ loading --
    @classmethod
    def from_xplane(cls, path: str) -> "Trace":
        import jax
        pd = jax.profiler.ProfileData.from_file(path)
        ops, async_ops, spans = {}, {}, []
        for plane in pd.planes:
            if plane.name.startswith(DEVICE_PREFIX):
                lines = {ln.name: ln for ln in plane.lines}
                if OPS_LINE not in lines:
                    raise ValueError(f"{plane.name}: no {OPS_LINE!r} line in "
                                     f"{sorted(lines)}")
                ops[plane.name] = [(op_name(e.name), e.start_ns, e.end_ns)
                                   for e in lines[OPS_LINE].events]
                if ASYNC_LINE in lines:
                    async_ops[plane.name] = [
                        (op_name(e.name), e.start_ns, e.end_ns)
                        for e in lines[ASYNC_LINE].events]
            elif plane.name.startswith("/host:"):
                spans += [(e.name, e.start_ns, e.end_ns)
                          for ln in plane.lines for e in ln.events
                          if e.name.startswith(SPAN_PREFIX)]
        if not ops:
            raise ValueError(f"{path}: no device plane named {DEVICE_PREFIX}*")
        win = [s for s in spans if s[0] == WINDOW_SPAN]
        if len(win) != 1:
            raise ValueError(f"{path}: expected one {WINDOW_SPAN} span, "
                             f"found {len(win)}")
        return cls(ops, spans, win[0][1:], async_ops)

    def to_json(self) -> str:
        return json.dumps({"window": [self.lo, self.hi], "ops": self.ops,
                           "async_ops": self.async_ops, "spans": self.spans})

    @classmethod
    def from_json(cls, text: str) -> "Trace":
        d = json.loads(text)
        return cls(d["ops"], d["spans"], tuple(d["window"]),
                   d.get("async_ops"))

    # ---------------------------------------------------------- reduction --
    @property
    def devices(self) -> list[str]:
        return sorted(self.ops)

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    def busy_intervals(self, dev: str) -> list[tuple[float, float]]:
        return merge([(s, e) for n, s, e in self.ops[dev]
                      if not CONTAINER.search(n)], self.lo, self.hi)

    def busy_s(self) -> float:
        """Seconds in which some op (not a container) ran, averaged over
        devices."""
        tot = sum(e - s for dev in self.devices
                  for s, e in self.busy_intervals(dev))
        return tot * 1e-9 / len(self.devices)

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s

    def matching(self, pattern: str) -> list[tuple[str, str, float, float]]:
        """``(device, name, start, end)`` of every op in the window whose
        name matches ``pattern`` (``re.search``)."""
        rx = re.compile(pattern)
        return [(dev, name, max(s, self.lo), min(e, self.hi))
                for dev in self.devices
                for name, s, e in self.ops[dev] + self.async_ops.get(dev, [])
                if rx.search(name) and min(e, self.hi) > max(s, self.lo)]

    def time_s(self, pattern: str) -> float:
        """Summed device seconds of the matching ops, averaged over devices
        (nested or overlapping matches are counted once)."""
        per_dev = defaultdict(list)
        for dev, _, s, e in self.matching(pattern):
            per_dev[dev].append((s, e))
        tot = sum(e - s for ivs in per_dev.values()
                  for s, e in merge(ivs, self.lo, self.hi))
        return tot * 1e-9 / len(self.devices)

    def top_ops(self, k: int = 10) -> list[list]:
        """The ``k`` op names with the most device seconds (per device)."""
        tot: dict[str, float] = defaultdict(float)
        for dev in self.devices:
            for name, s, e in self.ops[dev]:
                s, e = max(s, self.lo), min(e, self.hi)
                if e > s and not CONTAINER.search(name):
                    tot[name] += (e - s) * 1e-9 / len(self.devices)
        return [[n, v] for n, v in sorted(tot.items(), key=lambda kv: -kv[1])[:k]]

    def gaps(self, dev: str) -> list[tuple[float, float]]:
        busy = self.busy_intervals(dev)
        edges = [self.lo] + [x for iv in busy for x in iv] + [self.hi]
        return [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]

    def span_at(self, t: float) -> str:
        """The benchmark span open at ``t`` (the latest to start before it),
        or ``none`` when the host was between spans."""
        i = bisect.bisect_right(self._starts, t) - 1
        if i >= 0 and t < self._inner[i][1]:
            return self._inner[i][2]
        return "none"

    def idle_by_span(self, k: int = 10) -> list[list]:
        """Idle seconds (per device) by the host span open at each gap's
        midpoint, largest first."""
        tot: dict[str, float] = defaultdict(float)
        for dev in self.devices:
            for s, e in self.gaps(dev):
                tot[self.span_at((s + e) / 2)] += (e - s) * 1e-9 / len(
                    self.devices)
        return [[n, v] for n, v in sorted(tot.items(), key=lambda kv: -kv[1])[:k]]
