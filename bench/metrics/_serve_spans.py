"""Shared by the readers of the serving engine's own spans (``serve.*``,
``repro/runtime/trace.py``): device-idle time put down to the innermost
engine span that covers it, on the trace's clock."""

import bisect
from collections import defaultdict

import devtrace
import enginetrace


def engine_spans(r):
    """The engine's spans in the traced window, clipped to it; empty
    when the program has none."""
    lo, hi = r.window()
    return [(n, max(s, lo), min(e, hi)) for n, s, e in enginetrace.of(r).spans
            if s < hi and e > lo]


def idle_by_span(r):
    """{span name: seconds of the window in which the device ran nothing
    and that span was the innermost engine span}, averaged over the
    cell's chips; None when the program has no spans.  An idle instant
    counts once, so the values sum to at most the window's idle time."""
    spans = engine_spans(r)
    if not spans:
        return None
    lo, hi = r.window()
    devs = r.devices()
    out = defaultdict(float)
    for d in devs:
        busy = _busy_until(devtrace.merge(r.trace.ops.get(d, []), lo, hi))

        def idle(a, b):
            return (b - a) - (busy(b) - busy(a))

        # spans nest on the engine's thread: a span's own idle time is
        # its idle time less that of its direct children
        stack = []
        for n, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
            while stack and stack[-1][2] <= s:
                stack.pop()
            own = idle(s, e)
            if stack:
                out[stack[-1][0]] -= own
            out[n] += own
            stack.append((n, s, e))
    return {n: v / len(devs) for n, v in out.items()}


def idle_share(r, names):
    """Percent of the window idle under the innermost spans ``names``."""
    by = idle_by_span(r)
    if by is None:
        return None
    lo, hi = r.window()
    return 100.0 * sum(by.get(n, 0.0) for n in names) / (hi - lo)


def _busy_until(merged):
    """t -> seconds of ``merged`` (disjoint, sorted) before t."""
    starts = [a for a, _ in merged]
    cum = [0.0]
    for a, b in merged:
        cum.append(cum[-1] + b - a)

    def f(t):
        i = bisect.bisect_right(starts, t) - 1
        if i < 0:
            return 0.0
        a, b = merged[i]
        return cum[i] + min(t, b) - a
    return f
