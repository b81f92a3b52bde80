"""Device time of the expert layer in one decode call: over the runs of
``jit_serve_decode`` that start in the traced window, the union of the
run's operations whose scope path holds ``moe`` (the held-expert
layer's router, held experts and shared experts), averaged over the
runs (device 0 of the cell)."""

import bisect

import devtrace
import enginetrace


def read(r):
    if r.kind != "serve" or r.trace is None:
        return None
    lo, hi = r.window()
    dev = r.devices()[0]
    runs = [(s, e) for n, s, e in r.trace.modules.get(dev, [])
            if enginetrace.program(n) == "jit_serve_decode" and lo <= s < hi]
    et = enginetrace.of(r)
    moe = [op for op in r.trace.ops.get(dev, [])
           if "moe" in et.scope_of(dev, op).split("/")]
    if not runs or not moe:
        return None
    starts = [s for _, s, _ in moe]
    total = 0.0
    for s, e in runs:
        mine = moe[bisect.bisect_left(starts, s):bisect.bisect_left(starts, e)]
        total += sum(b - a for a, b in devtrace.merge(mine, s, e))
    return 1e3 * total / len(runs)
