"""The whole serving step's share of the chip's bf16 peak: model
operations of the prefill and decode work of every traced tick
(``counts.py``: prefilled positions past an aliased prefix, one decode
token per live slot) over the device-busy time of those ticks, in
percent."""

import counts
from _serve_ticks import ticks


def _prefill_flops(model, tick, sys_len):
    left = tick.get("hit_tokens", 0)
    total = 0.0
    for n, has_sys in tick["prefill"]:
        m = min(sys_len, left) if has_sys else 0
        left -= m
        total += counts.prefill_flops(model, m, n)
    return total


def read(r):
    if r.kind != "serve" or r.trace is None:
        return None
    sys_len = int(r.ctx.mix.get("system_len", 0))
    flops = busy = 0.0
    for t, b, _ in ticks(r):
        flops += _prefill_flops(r.model, t, sys_len)
        if t["decode_ctx"]:
            flops += counts.decode_flops(r.model, t["decode_ctx"])
        busy += b
    return 100.0 * flops / (busy * r.peaks["bf16_flops"]) if busy else None
