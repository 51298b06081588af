"""The reduction from a profiler trace to busy time, top operations and
idle gaps."""

import os

import pytest

from harness import trace

FIXTURE = os.path.join(os.path.dirname(__file__), "data",
                       "serve.xplane.pb.gz")


def test_merge_gaps_and_attribution():
    busy = trace.merge([(0, 10), (5, 20), (30, 40), (35, 36)])
    assert busy == [[0, 20], [30, 40]]
    assert trace.gaps(busy, -5, 50) == [(-5, 0), (20, 30), (40, 50)]
    spans = [(18, 28, "bench.fetch"), (28, 45, "bench.feed")]
    idle = trace.attribute([(20, 30), (40, 50), (60, 70)], spans)
    # a gap goes whole to the span that overlaps it most
    assert idle["bench.fetch"] == pytest.approx(10e-9)
    assert idle["bench.feed"] == pytest.approx(10e-9)
    assert idle[trace.UNLABELLED] == pytest.approx(10e-9)


def test_self_times_of_nested_events():
    events = [(0, 100, "while"), (10, 30, "a"), (40, 90, "b"),
              (50, 60, "c"), (120, 130, "a")]
    st = trace.self_times(events)
    assert st["while"] == pytest.approx(30e-9)
    assert st["a"] == pytest.approx(30e-9)
    assert st["b"] == pytest.approx(40e-9)
    assert st["c"] == pytest.approx(10e-9)


def _brute_busy(path):
    """Busy time by a plain sweep over every nanosecond boundary."""
    pd = trace.load(path)
    win = [(e.start_ns, e.start_ns + e.duration_ns)
           for p in pd.planes if p.name.startswith("/host")
           for line in p.lines for e in line.events
           if e.name == trace.WINDOW][0]
    evs = sorted((max(e.start_ns, win[0]), min(e.start_ns + e.duration_ns,
                                               win[1]))
                 for p in pd.planes if p.name.startswith(trace.DEVICE_PREFIX)
                 for line in p.lines if line.name == trace.OPS_LINE
                 for e in line.events
                 if e.start_ns + e.duration_ns > win[0] and e.start_ns < win[1])
    total, end = 0.0, float("-inf")
    for a, b in evs:
        if b > end:
            total += b - max(a, end)
            end = b
    return total * 1e-9, (win[1] - win[0]) * 1e-9


def test_recorded_trace():
    """A decode window traced on one TPU v5e, cut to a few steps."""
    got = trace.reduce(FIXTURE)
    busy, window = _brute_busy(FIXTURE)
    assert got.busy_s == pytest.approx(busy, rel=1e-9)
    assert got.window_s == pytest.approx(window, rel=1e-9)
    assert 0 < got.busy_s < got.window_s
    ops = [s for _, s in got.device_ops]
    assert ops == sorted(ops, reverse=True) and sum(ops) <= got.busy_s
    idle = sum(s for _, s in got.idle_gaps)
    assert idle == pytest.approx(got.window_s - got.busy_s, rel=1e-6)
    assert all(n.startswith("bench.") or n == trace.UNLABELLED
               for n, _ in got.idle_gaps)
