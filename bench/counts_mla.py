"""Model operations and bytes from shapes, for the latent-attention MoE
decoder (DeepSeek-V2) as one chip holds it: every layer's attention,
the leading dense layers, each MoE layer's router and shared experts,
and only the routed experts held here.

Counts are of the model, not of the compiled program, as in
``counts.py``: a matrix product of an (m, k) by (k, n) is 2mkn.  A
token's routed work counts only its token-expert pairs on held
experts, which the engine counts (``moe_held_pairs``).  Attention at a
position with c visible keys is, in the prefill (keys and values
expanded from the latent), 2 * heads * c * (qk width + v width); in
the decode (absorbed: every head attends over the latent row),
2 * heads * c * (2 * kv_lora_rank + qk_rope_head_dim).  The expansion
of an aliased prefix's keys and values, element-wise work and any
recomputation are not counted.
"""

from __future__ import annotations

from typing import Dict, Iterable

import numpy as np


def attn_params(cfg) -> int:
    """Attention weights one layer multiplies every token by: q, the
    latent row, its up-projection (keys and values in the prefill, the
    absorbed query and context in the decode) and the output."""
    d, H, r = cfg["d_model"], cfg["n_heads"], cfg["kv_lora_rank"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    return d * H * (nope + rope) + d * (r + rope) + r * H * (nope + dv) \
        + H * dv * d


def expert_params(cfg) -> int:
    """Weights of one routed expert (gate, up and down)."""
    return 3 * cfg["d_model"] * cfg["moe_d_ff"]


def token_params(cfg) -> Dict[str, int]:
    """Weights every token is multiplied by: in the dense layers, and in
    the MoE layers outside the routed experts (router, shared experts)."""
    d, L, Ld = cfg["d_model"], cfg["n_layers"], cfg["first_dense_layers"]
    sf = cfg["moe_d_ff"] * cfg["n_shared_experts"]
    dense = attn_params(cfg) + 3 * d * cfg["d_ff"]
    moe = attn_params(cfg) + d * cfg["n_experts"] + 3 * d * sf
    return {"dense": Ld * dense, "moe": (L - Ld) * moe}


def attn_flops(cfg, positions: np.ndarray, absorbed: bool) -> float:
    """Scores and context of tokens at absolute ``positions`` (each
    sees ``pos + 1`` keys), summed over layers."""
    pos = np.asarray(positions, np.float64)
    H, r = cfg["n_heads"], cfg["kv_lora_rank"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    width = 2 * r + rope if absorbed else nope + rope + dv
    return cfg["n_layers"] * 2 * H * width * float((pos + 1).sum())


def forward_flops(cfg, positions: np.ndarray, head_rows: int,
                  pairs: int, absorbed: bool) -> float:
    """Forward operations of the tokens at ``positions`` with ``pairs``
    token-expert pairs on held experts, the output head applied to
    ``head_rows`` of them."""
    n = len(positions)
    per_tok = 2 * sum(token_params(cfg).values())
    return (n * per_tok + pairs * 2 * expert_params(cfg)
            + attn_flops(cfg, positions, absorbed)
            + head_rows * 2 * cfg["d_model"] * cfg["vocab_size"])


def prefill_flops(cfg, start: int, end: int, pairs: int) -> float:
    """Prefill of positions ``[start, end)`` (``start`` tokens already in
    the cache); logits for the last position only."""
    return forward_flops(cfg, np.arange(start, end), 1, pairs, False)


def decode_flops(cfg, ctx_lens: Iterable[int], pairs: int) -> float:
    """One decode call: one token per slot, the slot's new token at
    position ``ctx - 1`` for each ``ctx`` in ``ctx_lens``."""
    pos = np.asarray(list(ctx_lens), np.int64) - 1
    return forward_flops(cfg, pos, len(pos), pairs, True)


def param_bytes(cfg, bytes_per: int = 2) -> Dict[str, int]:
    """Weight bytes held here: the layers (router in float32, norms
    left out), the embedding table and the head."""
    d, V = cfg["d_model"], cfg["vocab_size"]
    L, Ld = cfg["n_layers"], cfg["first_dense_layers"]
    held = cfg.get("n_routed_experts") or cfg["n_experts"]
    tp = token_params(cfg)
    router = (L - Ld) * d * cfg["n_experts"]
    layers = (tp["dense"] + tp["moe"] - router
              + (L - Ld) * held * expert_params(cfg)) * bytes_per \
        + router * 4
    return {"layers": layers, "embed": V * d * bytes_per,
            "head": (0 if cfg.get("tie_embeddings") else V * d * bytes_per)}


def latent_bytes_per_token(cfg, bytes_per: int = 2) -> int:
    """One token's cache: a latent row in every layer."""
    return cfg["n_layers"] * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) \
        * bytes_per


def decode_bytes(cfg, ctx_lens: Iterable[int]) -> float:
    """Least bytes a decode call moves: every held weight and the head
    once, one embedding row per slot, and each slot's latent rows."""
    ctx = np.asarray(list(ctx_lens), np.int64)
    pb = param_bytes(cfg)
    head = pb["head"] or pb["embed"]
    return float(pb["layers"] + head + len(ctx) * cfg["d_model"] * 2
                 + latent_bytes_per_token(cfg) * float(ctx.sum()))
