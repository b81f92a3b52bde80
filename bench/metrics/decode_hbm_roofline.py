"""The decode program against its roofline: over traced ticks that only
decoded, the least time of each call (every layer weight and the head
read once, each live slot's cached keys and values read, at the chip's
HBM bandwidth, or its operations at peak if that is longer), summed,
over the program's summed device time, in percent."""

import counts
from _serve_ticks import decode_only


def read(r):
    if r.kind != "serve" or r.trace is None:
        return None
    least = spent = 0.0
    for t, dur in decode_only(r):
        least += max(counts.decode_bytes(r.model, t["decode_ctx"])
                     / r.peaks["hbm_bytes_per_s"],
                     counts.decode_flops(r.model, t["decode_ctx"])
                     / r.peaks["bf16_flops"])
        spent += dur
    return 100.0 * least / spent if spent else None
