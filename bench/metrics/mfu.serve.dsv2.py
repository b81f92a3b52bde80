"""The whole serving step's share of the chip's bf16 peak, for the
latent-attention MoE model: model operations (``counts_mla.py``) of the
prefill and decode work of every traced tick (prefilled positions past
an aliased prefix, one decode token per live slot, and the routed work
of the tick's token-expert pairs on held experts, from the engine's
``serve.moe`` marks) over the device-busy time of those ticks, in
percent."""

import counts_mla
from _moe_pairs import marks, per_tick
from _serve_ticks import ticks


def read(r):
    if r.kind != "serve" or r.trace is None or not marks(r):
        return None
    sys_len = int(r.ctx.mix.get("system_len", 0))
    got = ticks(r)
    flops = busy = 0.0
    for (t, b, _), pairs in zip(got, per_tick(r, [t for t, _, _ in got])):
        left = t.get("hit_tokens", 0)
        for n, has_sys in t["prefill"]:
            m = min(sys_len, left) if has_sys else 0
            left -= m
            flops += counts_mla.prefill_flops(r.model, m, n, 0)
        if t["decode_ctx"]:
            flops += counts_mla.decode_flops(r.model, t["decode_ctx"], 0)
        flops += (sum(pairs.values()) * 2
                  * counts_mla.expert_params(r.model))
        busy += b
    return 100.0 * flops / (busy * r.peaks["bf16_flops"]) if busy else None
