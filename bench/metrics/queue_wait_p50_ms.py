"""Median over the requests due in the window of the wait from a
request's due time to the start of the engine tick that admitted it
(host clock)."""

import numpy as np


def read(r):
    if r.kind != "serve" or not r.res["qwait"]:
        return None
    return float(np.percentile(r.res["qwait"], 50)) * 1e3
