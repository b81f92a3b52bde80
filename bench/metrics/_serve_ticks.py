"""Shared by the serving readers: the traced engine ticks, each with its
device time, on the trace's clock."""

import devtrace


def ticks(r):
    """[(tick, busy seconds, longest program run)] of the traced ticks
    (device 0 of the cell)."""
    lo, hi = r.window()
    dev = r.devices()[0]
    merged = devtrace.merge(r.trace.ops.get(dev, []), lo, hi)
    mods = r.trace.modules.get(dev, [])
    out = []
    for t in r.res["ticks"]:
        a, b = lo + t["start"], lo + t["end"]
        if b > hi:
            break
        runs = devtrace.within(mods, a, b)
        longest = max((e - s for _, s, e in runs), default=0.0)
        out.append((t, devtrace.busy_between(merged, a, b), longest))
    return out


def decode_only(r):
    """Traced ticks that admitted nothing and decoded: (tick, decode
    program seconds)."""
    return [(t, longest) for t, _, longest in ticks(r)
            if not t["prefill"] and t["decode_ctx"] and longest > 0]
