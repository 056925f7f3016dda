"""The trace reduction, on a trace recorded on a TPU v5e and on events made
by hand.

``data/tpu_probe.xplane.pb`` was recorded on one chip: a jitted bf16
``tanh(a @ a) @ a`` (1024 x 1024) run once before ``bench.window`` and three
times inside it, each run inside ``bench.step``, then ``bench.wait_outputs``
until ready and ``bench.wait_arrival`` for a 3 ms sleep.
"""
import pathlib

import pytest

from bench import trace
from bench.trace import Event

PROBE = pathlib.Path(__file__).resolve().parent / "data" / "tpu_probe.xplane.pb"
DEV = "/device:TPU:0"


@pytest.fixture(scope="module")
def probe():
    return trace.reduce(trace.load_events(str(PROBE)))


def test_recorded_trace_busy_time(probe):
    assert probe.chips == 1
    assert probe.window_s == pytest.approx(0.01347482)
    # Two of the three runs in the window; the first began (by the device's
    # clock) a fraction of a millisecond before the window span opened.
    assert probe.busy_s == pytest.approx(48.434e-6, rel=1e-3)


def test_recorded_trace_top_ops_are_named_by_program(probe):
    names = [n for n, _ in probe.device_ops]
    assert names[:2] == ["jit__lambda:%fusion", "jit__lambda:%convolution_tanh_fusion"]
    assert sum(s for _, s in probe.device_ops) == pytest.approx(probe.busy_s, rel=1e-3)


def test_recorded_trace_gives_every_op_its_device_seconds(probe):
    assert sum(probe.op_seconds.values()) == pytest.approx(probe.busy_s, rel=1e-3)
    top = sorted(probe.op_seconds.items(), key=lambda kv: -kv[1])[:trace.TOP]
    assert probe.device_ops == top


def test_recorded_trace_gaps_are_named_by_host_span(probe):
    label, seconds = probe.idle_gaps[0]
    assert label == "wait_arrival" and 0.004 < seconds < 0.005
    assert {g for g, _ in probe.idle_gaps} <= {"wait_arrival", "wait_outputs", "step", "none"}


def test_reduction_of_made_events():
    ms = 1e6
    events = [
        Event("/host:CPU", "python3", "bench.window", 0, 10 * ms),
        Event("/host:CPU", "python3", "bench.step", 0, 1 * ms),
        Event("/host:CPU", "python3", "bench.wait_outputs", 1 * ms, 3 * ms),
        Event("/host:CPU", "python3", "bench.wait_arrival", 4 * ms, 6 * ms),
        Event(DEV, "XLA Modules", "jit_fused(123)", 1 * ms, 5 * ms),
        Event(DEV, "XLA Ops", "%while.1 = (...) while(...)", 1 * ms, 2 * ms),
        Event(DEV, "XLA Ops", "%fusion.7 = bf16[] fusion()", 1.5 * ms, 0.5 * ms),  # in the loop
        Event(DEV, "XLA Ops", "%fusion.8 = bf16[] fusion()", 5 * ms, 1 * ms),
        Event(DEV, "XLA Ops", "%before = f32[] fusion()", -2 * ms, 1 * ms),  # outside the window
    ]
    s = trace.reduce(events)
    assert s.window_s == pytest.approx(0.010)
    assert s.busy_s == pytest.approx(0.003)
    assert s.device_ops == [("jit_fused:%while.1", pytest.approx(0.002)),
                            ("jit_fused:%fusion.8", pytest.approx(0.001))]
    assert s.op_seconds == dict(s.device_ops)
    # Every op is in ``op_seconds``; the breakdown keeps the longest.
    assert trace.reduce(events, top=1).op_seconds == s.op_seconds
    assert s.idle_gaps == [("wait_arrival", pytest.approx(0.004)),
                           ("wait_outputs", pytest.approx(0.002)),
                           ("step", pytest.approx(0.001))]


def test_union_and_clip():
    assert trace.union([(3, 4), (0, 2), (1, 3)]) == [(0, 4)]
    assert trace.clip([(0, 4), (6, 8)], 1, 7) == [(1, 4), (6, 7)]
