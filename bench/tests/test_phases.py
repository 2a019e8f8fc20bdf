"""The engine-phase reduction: device seconds by ``engine.<phase>`` on a
hand-made trace and phase map with exact answers, the innermost scope,
the refusal to guess, and, on the CPU, that the program ``bench/phases.py``
rebuilds from shapes is the one each solve cell compiles in set-up."""
import pytest

from bench import phases
from bench.trace import Trace
from conftest import tiny_cell

# Instruction names without metadata, with an engine scope, and with two
# nested scopes (the pipelined driver's next packet inside the ring).
HLO = """\
HloModule jit_solve, is_scheduled=true

%fused_computation.1 (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %mul.1 = f32[4]{0} multiply(%p, %p), metadata={op_name="jit(f)/engine.loop/while/body/engine.metrics/mul"}
}

ENTRY %main (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0)
  %gram_packet_sampled_pallas.8 = f32[4]{0} custom-call(%x), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/engine.loop/while/body/engine.packet/jit(gram_packet_sampled_pallas)/pallas_call"}
  %fusion.2 = f32[4]{0} fusion(%x), kind=kLoop, calls=%fused_computation.1
  %custom-call.40 = f32[4]{0} custom-call(%x), custom_call_target="Cholesky", metadata={op_name="jit(f)/engine.loop/while/body/engine.sweep/jit(_cholesky)/cholesky"}
  %collective-permute.3 = f32[4]{0} collective-permute(%x), source_target_pairs={{0,1},{1,0}}, metadata={op_name="jit(f)/engine.loop/while/body/engine.reduce/engine.packet/ppermute"}
  %copy.9 = f32[4]{0} copy(%x)
  ROOT %add.5 = f32[4]{0} add(%x, %x), metadata={op_name="jit(f)/engine.loop/while/body/add"}
}
"""


def phase_map():
    from repro.core.hlo_analysis import engine_phase_map
    return engine_phase_map(HLO)


def hand_trace(extra=()):
    # Window [0, 100) ns on two devices; the scan's while op encloses all.
    ops = {
        "/device:TPU:0": [["while.7", 0, 100],
                          ["gram_packet_sampled_pallas.8 [tpu_custom_call]",
                           0, 40],
                          ["custom-call.40 [Cholesky]", 40, 50],
                          ["fusion.2", 50, 60],
                          ["copy.9", 60, 65],
                          ["add.5", 65, 70]],
        "/device:TPU:1": [["gram_packet_sampled_pallas.8 [tpu_custom_call]",
                           -20, 30],
                          ["collective-permute.3", 30, 50],
                          ["fusion.2", 90, 120], *extra],
    }
    return Trace(ops, [["bench.window", 0, 100]], (0, 100))


def test_phase_map_reads_innermost_scope():
    m = phase_map()
    assert m["gram_packet_sampled_pallas.8"] == "packet"
    assert m["custom-call.40"] == "sweep"
    # Innermost wins: the next step's packet contracted inside the ring.
    assert m["collective-permute.3"] == "packet"
    # A fusion without an op_name takes the one phase it fuses.
    assert m["fusion.2"] == "metrics"
    # The scan's own counter is the loop; a compiler-made copy has none.
    assert m["add.5"] == "loop"
    assert m["copy.9"] is None


def test_device_seconds_by_phase():
    secs = phases.attribute(hand_trace(), phase_map())
    # Per device, clipped to the window, the while op left out:
    # packet (40 + 30 + 20) / 2, sweep 10 / 2, metrics (10 + 10) / 2,
    # unscoped 5 / 2, loop 5 / 2.
    assert secs == pytest.approx({"packet": 45e-9, "sweep": 5e-9,
                                  "metrics": 10e-9, phases.UNSCOPED: 2.5e-9,
                                  "loop": 2.5e-9})
    busy = hand_trace().busy_s()
    assert sum(secs.values()) == pytest.approx(busy)


def test_unknown_op_refuses_to_guess():
    notes = []
    trace = hand_trace(extra=[["fusion.99", 60, 70]])
    assert phases.attribute(trace, phase_map(), notes.append) is None
    assert "fusion.99" in notes[0]
    # An unknown op outside the window is no reason to refuse.
    trace = hand_trace(extra=[["fusion.99", 100, 110]])
    assert phases.attribute(trace, phase_map()) is not None


class _Run:
    def __init__(self, trace, kind="closed_loop"):
        self.trace, self.traffic = trace, {"kind": kind}
        self.cell, self.cfg = {"chips": 1}, {"impl": "pallas"}
        self.facts, self.notes = {}, []

    def note(self, msg):
        self.notes.append(msg)


def test_share_of_busy_time(monkeypatch):
    calls = []
    monkeypatch.setattr(phases, "program_text",
                        lambda *a: calls.append(a) or HLO)
    run = _Run(hand_trace())
    busy = run.trace.busy_s()
    assert phases.share(run, "metrics") == pytest.approx(
        100 * 10e-9 / busy)
    assert phases.share(run, "sweep") == pytest.approx(100 * 5e-9 / busy)
    assert phases.share(run, "update") == 0.0
    assert len(calls) == 1                      # rebuilt once per run
    assert any("unscoped" in n for n in run.notes)
    assert phases.share(_Run(None), "sweep") is None
    assert phases.share(_Run(hand_trace(), "open_loop"), "sweep") is None
    # A program whose HLO names no phase (the parent of the scopes).
    monkeypatch.setattr(phases, "program_text",
                        lambda *a: HLO.replace("engine.", "stage."))
    assert phases.share(_Run(hand_trace()), "sweep") is None


@pytest.mark.parametrize("name", ["realsim-primal.solve", "news20-dual.solve"])
def test_rebuilt_program_is_the_compiled_solve(name, cpu_only):
    import jax
    from bench import harness
    from bench.loops.closed_loop import Loop
    from repro.core.hlo_analysis import engine_phase_map
    _, cell, cfg, traffic, _ = tiny_cell(name)
    devices = jax.devices()[:cell["chips"]]
    loop = Loop(cfg, traffic, 7 + 2 ** 33, devices, "pallas_interpret")
    loop.setup(harness.Run(cell, cfg, traffic, 7), 1.0)
    compiled = engine_phase_map(loop.solve.as_text())
    rebuilt = engine_phase_map(phases.program_text(cfg, traffic, devices,
                                                   "pallas_interpret"))
    assert rebuilt == compiled
    assert {"packet", "assemble", "sweep", "update", "metrics"} <= \
        set(rebuilt.values())
