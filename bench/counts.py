"""Model operations and bytes from shapes, for the dense (Qwen3) block.

Counts are of the model, not of the compiled program: a matrix product
of an (m, k) by (k, n) is 2mkn; causal attention at a position with c
visible keys is 4 * heads * head_dim * c (scores and context).
Element-wise work is not counted, nor is any recomputation.
"""

from __future__ import annotations

from typing import Dict, Iterable

import numpy as np


def layer_matmul_params(cfg) -> int:
    """Weights one layer multiplies every token by (no norms)."""
    d, H, K, hd, ff = (cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"],
                       cfg["head_dim"], cfg["d_ff"])
    return d * H * hd * 2 + d * K * hd * 2 + d * ff * 3


def attn_flops(cfg, positions: np.ndarray) -> float:
    """Attention score and context operations of tokens at absolute
    ``positions`` (each sees ``pos + 1`` keys), summed over layers."""
    pos = np.asarray(positions, np.float64)
    return (cfg["n_layers"] * 4 * cfg["n_heads"] * cfg["head_dim"]
            * float((pos + 1).sum()))


def forward_flops(cfg, positions: np.ndarray, head_rows: int) -> float:
    """Forward operations of the tokens at ``positions``, with the output
    head applied to ``head_rows`` of them."""
    n = len(positions)
    per_tok = 2 * layer_matmul_params(cfg)
    return (n * cfg["n_layers"] * per_tok + attn_flops(cfg, positions)
            + head_rows * 2 * cfg["d_model"] * cfg["vocab_size"])


def prefill_flops(cfg, start: int, end: int) -> float:
    """Prefill of positions ``[start, end)`` (``start`` tokens already in
    the cache); logits for the last position only."""
    return forward_flops(cfg, np.arange(start, end), 1)


def decode_flops(cfg, ctx_lens: Iterable[int]) -> float:
    """One decode call: one token per slot, the slot's new token at
    position ``ctx - 1`` for each ``ctx`` in ``ctx_lens``."""
    pos = np.asarray(list(ctx_lens), np.int64) - 1
    return forward_flops(cfg, pos, len(pos))


def param_bytes(cfg, bytes_per: int = 2) -> Dict[str, int]:
    """Weight bytes: the layers, the embedding table and the head."""
    d, V, L = cfg["d_model"], cfg["vocab_size"], cfg["n_layers"]
    return {"layers": L * layer_matmul_params(cfg) * bytes_per,
            "embed": V * d * bytes_per,
            "head": (0 if cfg.get("tie_embeddings") else V * d * bytes_per)}


def kv_bytes_per_token(cfg, bytes_per: int = 2) -> int:
    return 2 * cfg["n_layers"] * cfg["n_kv_heads"] * cfg["head_dim"] \
        * bytes_per


def decode_bytes(cfg, ctx_lens: Iterable[int]) -> float:
    """Least bytes a decode call moves: every layer weight and the head
    once, one embedding row per slot, each slot's cached keys and values
    read, and the new row written."""
    ctx = np.asarray(list(ctx_lens), np.int64)
    pb = param_bytes(cfg)
    head = pb["head"] or pb["embed"]
    return float(pb["layers"] + head + len(ctx) * cfg["d_model"] * 2
                 + kv_bytes_per_token(cfg) * float(ctx.sum()))
