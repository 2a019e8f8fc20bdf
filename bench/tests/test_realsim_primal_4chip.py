"""The 4-chip cell ``realsim-primal-4chip.solve``: its readers on hand-made
4-device traces with answers counted by hand, the all-reduce's work, and a
tiny rehearsal of the cell on four virtual CPU devices, in a process of its
own (``python test_realsim_primal_4chip.py [none|no_exchange]``), that is
correct with the packet all-reduce and not correct without it."""
import json
import os
import subprocess
import sys

if __name__ == "__main__":
    # Four virtual CPU devices, set before anything imports JAX.
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")
    os.environ["JAX_PLATFORMS"] = "cpu"
    _ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

import pytest

from bench import phases
from bench.harness import reader
from bench.peaks import peaks
from bench.trace import Trace
from bench.work import allreduce

CELL = "realsim-primal-4chip.solve"
DEVS = [f"/device:TPU:{i}" for i in range(4)]
V5E = peaks("TPU v5 lite")
# One packet: sb = 256 rows, G (256 x 256) and r (256) in one message.
WORDS = 256 * 257                      # 65,792
# 2 (P - 1) / P x 65,792 words x 4 bytes, P = 4: 394,752 bytes a chip;
# over 1.6e12 bit/s (200 GB/s): 1.97376 us.
LEAST_S = 394_752 * 8 / 1.6e12


class _Run:
    def __init__(self, trace, ops=None, shards=4):
        self.trace, self.peak = trace, V5E
        self.traffic = {"kind": "closed_loop", "shards": shards}
        self.cell, self.cfg = {"chips": shards}, {"impl": "pallas"}
        self.facts = {"packet": (256, 18078), "itemsize": 4}
        if ops is not None:
            self.facts["wire_ops"] = ops
        self.notes = []

    def note(self, msg):
        self.notes.append(msg)


def _trace(per_dev, window=(0, 100_000)):
    return Trace.from_json(json.dumps({
        "window": list(window), "spans": [["bench.window", *window]],
        "ops": {dev: per_dev(i) for i, dev in enumerate(DEVS)}}))


def test_allreduce_work_by_hand():
    assert allreduce.work(WORDS, 4, 4) == 394_752.0
    assert allreduce.work(WORDS, 1, 4) == 0.0
    assert allreduce.least_s(WORDS, 4, 4, V5E) == pytest.approx(1.97376e-6)


def test_all_reduce_ops_from_hlo():
    read_ops = reader_module("wire.reduce_roofline").all_reduce_ops
    hlo = (
        "  %psum_invariant.13 = f32[256,257]{1,0:T(8,128)S(1)} all-reduce("
        "%pad_maximum_fusion.2), channel_id=1, to_apply=%region_3.3\n"
        "  %all-reduce-start.1 = (f32[4]{0}, f32[4]{0}) all-reduce-start("
        "%a, %b), channel_id=2\n"
        "  ROOT %all-reduce-done.1 = f32[4]{0} all-reduce-done("
        "%all-reduce-start.1)\n"
        "  %fusion.2 = f32[4]{0} fusion(%all-reduce-done.1), kind=kLoop\n")
    assert read_ops(hlo) == {"psum_invariant.13": "sync",
                             "all-reduce-start.1": "start",
                             "all-reduce-done.1": ("done",
                                                   "all-reduce-start.1")}


def reader_module(name):
    import importlib.util
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_reduce_roofline_one_op():
    # Each chip: two packet all-reduces of 10 us and 30 us inside the
    # window, one reaching past its end (left out), and a kernel.
    def ops(i):
        return [["psum_invariant.13", 1_000, 11_000],
                ["gram_packet_sampled_pallas.8 [tpu_custom_call]",
                 11_000, 50_000],
                ["psum_invariant.13", 50_000, 80_000],
                ["psum_invariant.13", 95_000, 105_000]]
    run = _Run(_trace(ops), {"psum_invariant.13": "sync"})
    got = reader("metrics", "wire.reduce_roofline")(run)
    # 8 all-reduces over 4 x 40 us: 2 x 1.97376 / 40 = 9.8688 %.
    assert got == pytest.approx(100 * 2 * LEAST_S / 40e-6)
    assert got == pytest.approx(9.8688)


def test_reduce_roofline_start_done_pair():
    # Asynchronous: the start op at 1 us, the done op (the wait) from 3 to
    # 4 us; the all-reduce is in flight from 1 to 4 us, so 3 us, once a
    # chip.  The done alone (1 us) would read the impossible 197 %.
    def ops(i):
        return [["all-reduce-start.1", 1_000, 1_200],
                ["fusion.5", 1_200, 3_000],
                ["all-reduce-done.1", 3_000, 4_000]]
    pair = {"all-reduce-start.1": "start",
            "all-reduce-done.1": ("done", "all-reduce-start.1")}
    got = reader("metrics", "wire.reduce_roofline")(_Run(_trace(ops), pair))
    assert got == pytest.approx(100 * LEAST_S / 3e-6)
    assert got == pytest.approx(65.792)
    assert got < 100


def test_reduce_roofline_reads_nothing_without_a_wire():
    read = reader("metrics", "wire.reduce_roofline")
    ops = {"psum_invariant.13": "sync"}
    assert read(_Run(None, ops)) is None
    trace = _trace(lambda i: [["psum_invariant.13", 0, 10_000]])
    assert read(_Run(trace, ops, shards=1)) is None
    # A program without the all-reduce (nothing matches): no number.
    assert read(_Run(trace, {})) is None


HLO_BIND = """\
HloModule jit_solve, is_scheduled=true

ENTRY %main (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0)
  %copy_fusion.1 = f32[4]{0} fusion(%x), kind=kLoop, calls=%c, metadata={op_name="jit(f)/shard_map/engine.bind/reshape"}
  %gram_packet_sampled_pallas.8 = f32[4]{0} custom-call(%x), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/shard_map/engine.loop/while/body/engine.packet/pallas_call"}
  %psum_invariant.13 = f32[4]{0} all-reduce(%x), channel_id=1, to_apply=%r, metadata={op_name="jit(f)/shard_map/engine.loop/while/body/engine.reduce/psum_invariant"}
  ROOT %panel_apply_pallas.5 = f32[4]{0} custom-call(%x), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/shard_map/engine.loop/while/body/engine.update/pallas_call"}
}
"""


def test_bind_share(monkeypatch):
    # Each chip: the shard relayout 20 us, packet 50 us, all-reduce 10 us,
    # apply 10 us: busy 90 us, bind 20 / 90.
    def ops(i):
        return [["copy_fusion.1", 0, 20_000],
                ["gram_packet_sampled_pallas.8 [tpu_custom_call]",
                 20_000, 70_000],
                ["psum_invariant.13", 70_000, 80_000],
                ["panel_apply_pallas.5 [tpu_custom_call]", 80_000, 90_000]]
    monkeypatch.setattr(phases, "program_text", lambda *a: HLO_BIND)
    run = _Run(_trace(ops))
    assert reader("metrics", "engine.bind_share")(run) == pytest.approx(
        100 * 20 / 90)
    assert reader("metrics", "engine.sweep_share")(run) == 0.0
    # The same rebuilt program gives the all-reduce's opcode.
    assert reader("metrics", "wire.reduce_roofline")(run) == pytest.approx(
        100 * LEAST_S / 10e-6)
    assert run.facts["wire_ops"] == {"psum_invariant.13": "sync"}


def test_collective_share_by_hand():
    # The existing reader matches ops named after their opcode: an
    # all-reduce of 10 us in a 100 us window on each chip.
    def ops(i):
        return [["all-reduce.3", 10_000, 20_000],
                ["fusion.1", 20_000, 60_000]]
    got = reader("metrics", "wire.collective_share")(_Run(_trace(ops)))
    assert got == pytest.approx(10.0)


# ---------------------------------------------------- rehearsal, 4 devices --

def tiny_cell():
    """The cell cut to a size the CPU runs in seconds: d = 64 and n = 1,022
    (a ragged last shard before the 2 zero columns), s = 4, 64 iterations,
    4 index streams."""
    from bench import harness
    spec, cell, cfg, traffic, limits = harness.load_cell(CELL)
    cfg = {**cfg, "d": 64, "n": 1022, "s": 4}
    traffic = {**traffic, "iters": 64, "streams": 4}
    return spec, cell, cfg, traffic, limits


def _four(fault):
    out = subprocess.run([sys.executable, os.path.abspath(__file__), fault],
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_four_device_rehearsal_is_correct():
    res = _four("none")
    assert res["correct"] is True, res
    assert res["failed"] == 0 and res["compiles_in_window"] == 0
    # The rebuilt program: one all-reduce (in the scan body), the phases
    # the new readers read.
    assert list(res["wire_ops"].values()) == ["sync"], res["wire_ops"]
    assert {"bind", "packet", "reduce", "sweep", "update"} <= set(
        res["phases"])


def test_four_device_rehearsal_without_exchange_is_not_correct():
    res = _four("no_exchange")
    assert res["correct"] is False, res


if __name__ == "__main__":
    import time

    import jax

    from bench import harness
    from repro.core.hlo_analysis import engine_phase_map
    if sys.argv[1] == "no_exchange":
        # the packet all-reduce left out: each chip keeps its own share
        jax.lax.psum = lambda x, axis_name, **kw: x
    spec, cell, cfg, traffic, limits = tiny_cell()
    devs = jax.devices()[:cell["chips"]]
    run = harness.execute(cell, cfg, traffic, limits, 11 + 2 ** 33, 1.0,
                          False, devs, "pallas_interpret", time.perf_counter())
    text = phases.program_text(cfg, traffic, devs, "pallas_interpret")
    print(json.dumps({
        "correct": run.correct, "compared": run.compared,
        "failed": run.failed,
        "compiles_in_window": run.compiles_in_window["compiles"],
        "wire_ops": reader_module("wire.reduce_roofline").all_reduce_ops(text),
        "phases": sorted({p for p in engine_phase_map(text).values() if p})}))
