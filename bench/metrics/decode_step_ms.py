"""Device time of one decode call: in traced ticks that admitted
nothing, the longest program the chip ran in the tick (the decode
program; the rest are per-slot sampling), averaged over those ticks."""

from _serve_ticks import decode_only


def read(r):
    if r.kind != "serve" or r.trace is None:
        return None
    d = decode_only(r)
    return 1e3 * sum(x for _, x in d) / len(d) if d else None
