"""Plain float32 reference of the dense decoder (Qwen3: grouped-query
attention with per-head RMS qk-norm and rotary embedding, SwiGLU MLP,
pre-norm residual blocks, untied output head).

Departures from the published model, each matching the configuration
the program runs: norm scales are stored as offsets from 1, and the
depth is the configuration's.

:func:`served_logits` is the serving check: the full forward pass over
prompt plus served tokens, layer by layer with each layer's weights
drawn again from the seed, returning the logits at the positions that
chose each served token.
"""

from __future__ import annotations

from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from reference import common

LAYER_LEAVES = ("pre_norm", "mlp_norm", "attn/wq", "attn/wk", "attn/wv",
                "attn/wo", "attn/q_norm", "attn/k_norm", "mlp/wi", "mlp/wo")


def layer_shapes(cfg) -> Dict[str, tuple]:
    d, H, K, hd, ff = (cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"],
                       cfg["head_dim"], cfg["d_ff"])
    shp = {"pre_norm": (d,), "mlp_norm": (d,),
           "attn/wq": (d, H * hd), "attn/wk": (d, K * hd),
           "attn/wv": (d, K * hd), "attn/wo": (H * hd, d),
           "mlp/wi": (d, 2 * ff), "mlp/wo": (ff, d)}
    if cfg.get("qk_norm"):
        shp["attn/q_norm"] = (hd,)
        shp["attn/k_norm"] = (hd,)
    return {f"blocks/{k}": v for k, v in shp.items()}


def block(p: Dict[str, jax.Array], x, pos, cfg, prec):
    """One decoder layer on x (B, S, d)."""
    g = lambda k: p.get(f"blocks/{k}")
    attn = {k.split("/")[1]: g(k) for k in LAYER_LEAVES
            if k.startswith("attn/") and g(k) is not None}
    h = common.rms_norm(x, g("pre_norm"), cfg["norm_eps"])
    x = x + common.gqa_block(h, attn, pos, cfg, 0, prec)
    h = common.rms_norm(x, g("mlp_norm"), cfg["norm_eps"])
    return x + common.swiglu(h, g("mlp/wi"), g("mlp/wo"), prec)


def served_logits(cfg, seed: int, seqs: Sequence[np.ndarray],
                  at: Sequence[np.ndarray], prec: str = "f32",
                  pad_to: int = 512) -> List[np.ndarray]:
    """Logits rows (float32 numpy, ``len(at[i])`` x vocab) of sequence i
    at positions ``at[i]``.  All sequences run as one batch, padded at
    the end to a multiple of ``pad_to`` (causal: padding is never seen
    by a real position)."""
    n = len(seqs)
    S = max(len(s) for s in seqs)
    S = -(-S // pad_to) * pad_to
    toks = np.zeros((n, S), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    shapes = layer_shapes(cfg)
    d, V = cfg["d_model"], cfg["vocab_size"]
    with jax.default_matmul_precision("highest"):
        emb = common.draw_top(seed, cfg, "embed", (V, d))
        x = jnp.take(emb, jnp.asarray(toks), axis=0)
        del emb
        # weights are arguments, never closed over: a captured array
        # would be compiled into the program as a constant
        run = jax.jit(lambda p, x: block(
            p, x, jnp.broadcast_to(jnp.arange(S), (n, S)), cfg, prec))
        draw = jax.jit(lambda l: common.draw_layer(seed, cfg, shapes, l))
        for layer in range(cfg["n_layers"]):
            x = run(draw(layer), x)
        fn = common.draw_top(seed, cfg, "final_norm", (d,))
        head = common.draw_top(seed, cfg, "lm_head", (d, V))
        rows = [x[i, jnp.asarray(a)] for i, a in enumerate(at)]
        out = jax.jit(lambda r, fn, head: common.mm(
            common.rms_norm(r, fn, cfg["norm_eps"]), head, prec))
        return [np.asarray(out(r, fn, head)) for r in rows]
