"""The trace reduction: busy union, idle share, time by name pattern,
collective time and idle gaps by host span, on a hand-made trace with
exact answers and on a small trace recorded on a v5e."""
import json
import os

import pytest

from bench.trace import Trace, merge, op_name

DATA = os.path.join(os.path.dirname(__file__), "data")


def hand_trace():
    # Window [0, 100) ns on two devices.  dev0: a kernel at [10, 30) with a
    # nested fusion [20, 40) (union [10, 40)), an all-reduce [50, 60);
    # dev1: one kernel [0, 20) and ops reaching past the window edge.
    ops = {
        "/device:TPU:0": [["gram_packet_sampled_pallas.8", 10, 30],
                          ["fusion.1", 20, 40],
                          ["all-reduce.3", 50, 60]],
        "/device:TPU:1": [["gram_packet_sampled_pallas.8", 0, 20],
                          ["fusion.1", 90, 130],
                          ["copy.2", -10, 0],
                          ["while.7", 0, 100]],    # encloses its body's ops
    }
    spans = [["bench.window", 0, 100], ["bench.solve", 0, 45],
             ["bench.wait", 45, 100]]
    return Trace(ops, spans, (0, 100))


def test_op_name_from_hlo_text():
    assert op_name('%panel_apply_pallas.5 = f32[1,72704]{1,0} custom-call('
                   'f32[16] %p), custom_call_target="tpu_custom_call"') == \
        "panel_apply_pallas.5 [tpu_custom_call]"
    assert op_name("%fusion.3 = f32[16]{0} fusion(%x), kind=kLoop") == \
        "fusion.3"
    assert op_name("all-reduce.1") == "all-reduce.1"


def test_merge_clips_and_unions():
    assert merge([(5, 9), (1, 3), (2, 4), (8, 12)], 0, 10) == [(1, 4), (5, 10)]
    assert merge([(-5, -1)], 0, 10) == []


def test_busy_and_idle_share():
    t = hand_trace()
    # dev0 busy 30 + 10 = 40 ns, dev1 busy 20 + 10 = 30 ns: mean 35 ns.
    assert t.busy_s() == pytest.approx(35e-9)
    assert t.window_s == pytest.approx(100e-9)
    assert t.idle_share() == pytest.approx(0.65)


def test_time_by_pattern():
    t = hand_trace()
    # Kernel: 20 ns on each device -> 20 ns per device.
    assert t.time_s(r"^gram_packet_sampled(_cols)?_pallas\b") == \
        pytest.approx(20e-9)
    assert len(t.matching("packet_sampled")) == 2
    # Collectives: 10 ns on dev0, 0 on dev1 -> 5 ns per device.
    assert t.time_s("all-reduce|collective-permute") == pytest.approx(5e-9)
    assert t.matching("no-such-op") == []


def test_top_ops_per_device():
    top = dict(hand_trace().top_ops(10))
    assert top["gram_packet_sampled_pallas.8"] == pytest.approx(20e-9)
    assert top["fusion.1"] == pytest.approx(15e-9)     # (20 + 10) / 2
    assert "copy.2" not in top                        # outside the window
    assert "while.7" not in top


def test_idle_gaps_by_host_span():
    t = hand_trace()
    # A gap goes to the span open at its midpoint.  dev0 gaps: [0,10) at 5
    # -> solve, [40,50) at 45 -> wait, [60,100) -> wait; dev1: [20,90) at
    # 55 -> wait.
    by = dict(t.idle_by_span())
    assert by["bench.solve"] == pytest.approx(10 / 2 * 1e-9)
    assert by["bench.wait"] == pytest.approx((10 + 40 + 70) / 2 * 1e-9)


def test_json_round_trip():
    t = hand_trace()
    u = Trace.from_json(t.to_json())
    assert u.busy_s() == t.busy_s() and u.idle_by_span() == t.idle_by_span()


@pytest.mark.parametrize("name", sorted(
    f for f in os.listdir(DATA) if f.startswith("trace_")))
def test_recorded_trace(name):
    """A trace recorded on a v5e (reduced, trimmed to a window of a few
    solves): the kernels are found by name, busy time lies inside the
    window, and the idle gaps add up to the idle share."""
    with open(os.path.join(DATA, name)) as f:
        rec = json.load(f)
    t = Trace.from_json(json.dumps(rec["trace"]))
    assert 0 < t.busy_s() <= t.window_s
    assert t.time_s(rec["kernel_pattern"]) > 0
    idle = sum(v for _, v in t.idle_by_span(100))
    assert idle == pytest.approx(t.window_s - t.busy_s(), rel=1e-9)
    for key, want in rec["expect"].items():
        assert getattr(t, key)() == pytest.approx(want, rel=1e-9)
