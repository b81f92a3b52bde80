"""Share of the traced window in which no operation ran on the device
(1 - union of operation intervals over the window, averaged over the
cell's chips), in percent."""

import devtrace


def read(r):
    if r.kind != "serve" or r.trace is None:
        return None
    lo, hi = r.window()
    devs = r.devices()
    busy = sum(devtrace.busy(r.trace.ops[d], lo, hi) for d in devs)
    return 100.0 * (1.0 - busy / len(devs) / (hi - lo))
