"""The reduction from a profiler trace to metrics, on a trace recorded
on the CPU (four steps of a jitted matmul with waits between them) and
on hand-made intervals."""

from pathlib import Path

import pytest

import devtrace

DATA = Path(__file__).parent / "data" / "cpu_trace.xplane.pb"


def cpu_lines(plane, line):
    """The CPU client's threads stand in for a chip's op line."""
    return (0, "ops") if line.startswith("tf_XLAPjRtCpuClient") else None


@pytest.fixture(scope="module")
def trace():
    return devtrace.load(str(DATA), cpu_lines)


def test_spans_and_ops_are_read(trace):
    names = [s[0] for s in trace.spans]
    assert names.count("bench.step") == 4 and names.count("bench.wait") == 4
    assert names[0] == "bench.window"
    assert len(trace.ops[0]) > 4


def test_busy_idle_and_gaps(trace):
    lo, hi = trace.span("bench.window")
    busy = devtrace.busy(trace.ops[0], lo, hi)
    assert 0 < busy < hi - lo
    gaps = devtrace.gaps(trace.ops[0], lo, hi)
    assert abs(sum(e - s for s, e in gaps) + busy - (hi - lo)) < 1e-9
    # the four sleeps of 4 ms are the four longest gaps, inside waits
    top = devtrace.attribute(gaps, trace.spans, 4)
    assert [n for n, _ in top] == ["bench.wait"] * 4
    assert all(0.0039 < s < 0.02 for _, s in top)


def test_per_name_totals(trace):
    lo, hi = trace.span("bench.window")
    totals = devtrace.by_name(trace.ops[0], lo, hi)
    name, secs = devtrace.top(totals, 1)[0]
    assert name.startswith("dot")
    assert secs <= devtrace.busy(trace.ops[0], lo, hi) + 1e-9


def test_interval_arithmetic():
    iv = [("a", 0.0, 2.0), ("b", 1.0, 3.0), ("c", 5.0, 6.0)]
    assert devtrace.merge(iv) == [(0.0, 3.0), (5.0, 6.0)]
    assert devtrace.busy(iv, 0.5, 5.5) == pytest.approx(3.0)
    assert devtrace.gaps(iv, 0.0, 7.0) == [(3.0, 5.0), (6.0, 7.0)]
    merged = devtrace.merge(iv)
    assert devtrace.busy_between(merged, 2.5, 5.5) == pytest.approx(1.0)
    assert devtrace.within(iv, 1.0, 5.0) == [("b", 1.0, 3.0)]


def test_exposed_collectives():
    ops = [("fusion.1", 0.0, 1.0), ("all-reduce.3", 0.5, 2.0),
           ("all-gather-start", 3.0, 3.5), ("fusion.2", 3.2, 3.3)]
    # all-reduce hidden over [0.5, 1], exposed over [1, 2]; the gather
    # exposed over [3, 3.2] and [3.3, 3.5]
    assert devtrace.exposed_collectives(ops, 0.0, 4.0) == pytest.approx(1.4)
