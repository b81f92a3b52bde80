"""Device time of one admission's prefill: over the ``serve.prefill``
spans that start in the traced window, the union of the operations of
the ``jit_serve_prefill*`` programs that start inside each span (the
span holds the wait for the first sample, so the program has run by
its end), averaged over the spans (device 0 of the cell)."""

import bisect

import devtrace
import enginetrace
from _serve_spans import engine_spans


def read(r):
    if r.kind != "serve" or r.trace is None:
        return None
    lo, _ = r.window()
    spans = [(s, e) for n, s, e in engine_spans(r)
             if n == "serve.prefill" and s >= lo]
    if not spans:
        return None
    dev = r.devices()[0]
    et = enginetrace.of(r)
    ops = [op for op in r.trace.ops.get(dev, [])
           if et.program_of(dev, op).startswith("jit_serve_prefill")]
    starts = [s for _, s, _ in ops]
    total = 0.0
    for s, e in spans:
        mine = ops[bisect.bisect_left(starts, s):bisect.bisect_left(starts, e)]
        total += sum(b - a for a, b in devtrace.merge(mine))
    return 1e3 * total / len(spans) if total else None
