"""The trace reduction (bench/trace.py): busy-interval union, idle share and
the naming of idle gaps by host span, on hand-made events and on a small
trace recorded on the CPU (fixtures/cpu_window.xplane.pb: a `bench.window`
span holding three `bench.solve` spans, each a jitted matmul chain, each
followed by a 20 ms `bench.host` sleep)."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import trace  # noqa: E402
from bench.trace import Event  # noqa: E402

FIXTURE = Path(__file__).parent / "fixtures" / "cpu_window.xplane.pb"


def op(start, dur, plane="/device:TPU:0", name="fusion"):
    return Event(plane, "XLA Ops", name, float(start), float(dur))


def host(name, start, dur):
    return Event("/host:CPU", "python", name, float(start), float(dur))


def test_union_merges_overlaps_and_drops_empty():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)]) == \
        [(0, 3), (5, 8)]


def test_gaps_cover_the_window_outside_busy():
    busy = [(2, 4), (6, 7)]
    assert trace.gaps(busy, 0, 10) == [(0, 2), (4, 6), (7, 10)]
    assert trace.gaps([], 0, 10) == [(0, 10)]


def test_summary_by_hand():
    events = [
        host("bench.window", 100, 100),            # window [100, 200]
        host("bench.tick", 100, 60),                # host busy in a tick
        host("bench.submit", 160, 40),
        op(90, 20, name="scatter"),                 # clipped to [100, 110]
        op(105, 10, name="fusion"),                 # overlaps: union
        op(120, 30, name="scatter"),                # [120, 150]
        op(190, 20, plane="/device:TPU:1"),         # second device: [190, 200]
        Event("/device:TPU:0", "XLA Modules", "jit_solve(1)", 100.0, 50.0),
    ]
    s = trace.summarize(events)
    assert s.window_ns == (100.0, 200.0)
    assert s.n_devices == 2
    # device 0 busy 10 + 30 = 40 (union: the 105-115 op adds 5); device 1 10
    assert s.busy_ns == pytest.approx(((15 + 30) + 10) / 2)
    assert s.ops["scatter"] == pytest.approx(10 + 30)
    assert s.module_s("solve") == pytest.approx(50e-9)
    # device 0 idle: [115, 120] and [150, 160] in the tick, [160, 200] in
    # the submit span
    assert s.gaps == {"tick": pytest.approx(15.0),
                      "submit": pytest.approx(40.0)}
    bd = s.breakdown()
    assert bd["device_ops"][0][0] == "scatter"
    assert bd["idle_gaps"][0] == ["submit", pytest.approx(40e-9)]


def test_gap_outside_every_span_is_named_so():
    events = [host("bench.window", 0, 10), op(0, 4)]
    assert trace.summarize(events).gaps == {"no bench span": 6.0}


def test_one_window_span_is_required():
    with pytest.raises(ValueError):
        trace.summarize([op(0, 1)])
    with pytest.raises(ValueError):
        trace.summarize([host("bench.window", 0, 5), host("bench.window", 6, 5),
                         op(0, 1)])


def test_no_device_operation_is_an_error():
    with pytest.raises(ValueError):
        trace.summarize([host("bench.window", 0, 10)])


def test_recorded_cpu_trace():
    events = trace.load_events(str(FIXTURE))
    names = [e.name for e in events if e.name.startswith("bench.")]
    assert names.count("bench.window") == 1
    assert names.count("bench.solve") == 3 and names.count("bench.host") == 3
    s = trace.summarize(events, *trace.SELECTORS["cpu"])
    assert 0 < s.busy_s < s.window_s
    idle = s.window_s - s.busy_s
    assert sum(s.gaps.values()) * 1e-9 == pytest.approx(idle, rel=1e-9)
    # the three 20 ms sleeps are where the device waited
    assert s.gaps["host"] * 1e-9 >= 0.06
    assert s.gaps["host"] > 0.9 * sum(s.gaps.values())
    # device time lies inside the solve spans
    assert s.busy_ns <= s.spans["bench.solve"]
