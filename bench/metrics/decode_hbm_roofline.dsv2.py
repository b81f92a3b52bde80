"""The latent-attention decode program against its roofline: over
traced ticks that only decoded, the least time of each call (every held
weight and the head read once, each live slot's latent rows read, at
the chip's HBM bandwidth, or its operations at peak if that is longer;
``counts_mla.py``, routed work from the tick's held pairs), summed,
over the program's summed device time, in percent."""

import counts_mla
from _moe_pairs import marks, per_tick
from _serve_ticks import decode_only


def read(r):
    if r.kind != "serve" or r.trace is None or not marks(r):
        return None
    dec = decode_only(r)
    least = spent = 0.0
    for (t, dur), pairs in zip(dec, per_tick(r, [t for t, _ in dec])):
        least += max(counts_mla.decode_bytes(r.model, t["decode_ctx"])
                     / r.peaks["hbm_bytes_per_s"],
                     counts_mla.decode_flops(r.model, t["decode_ctx"],
                                             pairs.get("decode", 0))
                     / r.peaks["bf16_flops"])
        spent += dur
    return 100.0 * least / spent if spent else None
