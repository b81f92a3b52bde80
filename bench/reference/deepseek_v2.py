"""Plain float32 reference of DeepSeek-V2 (arXiv:2405.04434 and the
published ``modeling_deepseek.py``): multi-head latent attention with no
query LoRA — ``c_kv = kv_a_layernorm(x W_DKV)``, per-head keys
``[c_kv W_UK ; rope(k_pe)]`` and values ``c_kv W_UV`` expanded from the
latent, a YaRN-scaled rotary embedding of interleaved pairs — then
``first_dense_layers`` SwiGLU layers and MoE layers: a softmax router
over ``n_experts``, greedy top-k, weights not renormalized, a routed
scaling of 1, plus the shared experts as one SwiGLU.

The keys and values are expanded from the latent, the non-absorbed
form: the program's decode folds ``W_UK`` into the query and applies
``W_UV`` after the context instead, which this checks.

Departures from the published model, each matching the configuration
the program runs: norm scales are stored as offsets from 1; the router
weights are held in float32, as the program holds them; and each MoE
layer holds the chip's share of the routed experts (experts
``0..n_routed_experts-1`` of the router's ``n_experts``): the other
experts' part of the routed sum is left out, here as in the program.

:func:`served_logits` is the serving check, as ``qwen3.py``'s: the full
forward pass over prompt plus served tokens, layer by layer with each
layer's weights drawn again from the seed, attention in query blocks
and the held experts one at a time so that it fits on the chip.
"""

from __future__ import annotations

import math
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

import weights
from reference import common

F32 = ("router",)          # stored in float32 besides the norm scales


def _dtype(path: str, cfg):
    name = path.rsplit("/", 1)[-1]
    if name in F32:
        return jnp.dtype("float32")
    return common.param_dtype(path, cfg)


def attn_shapes(cfg) -> Dict[str, tuple]:
    d, H, r = cfg["d_model"], cfg["n_heads"], cfg["kv_lora_rank"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    return {"pre_norm": (d,), "mlp_norm": (d,),
            "attn/wq": (d, H * (nope + rope)), "attn/wkv_a": (d, r + rope),
            "attn/kv_norm/norm": (r,), "attn/wkv_b": (r, H * (nope + dv)),
            "attn/wo": (H * dv, d)}


def moe_shapes(cfg) -> Dict[str, tuple]:
    d, ff, E = cfg["d_model"], cfg["moe_d_ff"], cfg["n_experts"]
    Eh = cfg.get("n_routed_experts") or E
    sf = ff * cfg["n_shared_experts"]
    shp = {**attn_shapes(cfg), "moe/router": (d, E),
           "moe/wi": (Eh, d, 2 * ff), "moe/wo": (Eh, ff, d),
           "moe/shared_wi": (d, 2 * sf), "moe/shared_wo": (sf, d)}
    return {f"blocks/{k}": v for k, v in shp.items()}


def dense_shapes(cfg) -> Dict[str, tuple]:
    """The leading dense layers' leaves: one leaf each, stacked over
    those layers (``lead/...``, not under ``blocks``: drawn whole)."""
    Ld, d, ff = cfg["first_dense_layers"], cfg["d_model"], cfg["d_ff"]
    shp = {**attn_shapes(cfg), "mlp/wi": (d, 2 * ff), "mlp/wo": (ff, d)}
    return {f"lead/{k}": (Ld, *v) for k, v in shp.items()}


def yarn(cfg):
    """Inverse frequencies of the rotary dims, the scale of their
    cos/sin, and the softmax scale (``DeepseekV2YarnRotaryEmbedding``
    and ``DeepseekV2Attention.__init__``)."""
    dim, base = cfg["qk_rope_head_dim"], cfg["rope_theta"]
    f = cfg["yarn_factor"]
    freq_extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    freq_inter = 1.0 / (f * base ** (np.arange(0, dim, 2) / dim))

    def corr_dim(rot):
        return (dim * math.log(cfg["yarn_original_len"]
                               / (rot * 2 * math.pi))) / (2 * math.log(base))

    low = max(math.floor(corr_dim(cfg["yarn_beta_fast"])), 0)
    high = min(math.ceil(corr_dim(cfg["yarn_beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    inv_freq_mask = 1.0 - ramp
    inv = freq_inter * (1 - inv_freq_mask) + freq_extra * inv_freq_mask

    def mscale(m):
        return 1.0 if f <= 1 else 0.1 * m * math.log(f) + 1.0

    cs = mscale(cfg["yarn_mscale"]) / mscale(cfg["yarn_mscale_all_dim"])
    scale = (cfg["qk_nope_head_dim"] + dim) ** -0.5 \
        * mscale(cfg["yarn_mscale_all_dim"]) ** 2
    return jnp.asarray(inv, jnp.float32), cs, scale


def rope(x, pos, inv, cs):
    """``apply_rotary_pos_emb``: the dims are read in interleaved pairs
    (reordered evens-then-odds), then rotated half against half.
    x (B, S, h, dim), pos (B, S)."""
    B, S, h, dim = x.shape
    x = x.reshape(B, S, h, dim // 2, 2).swapaxes(-1, -2).reshape(B, S, h, dim)
    freqs = pos.astype(jnp.float32)[..., None] * inv           # (B,S,dim/2)
    emb = jnp.concatenate([freqs, freqs], axis=-1)[:, :, None]
    cos, sin = jnp.cos(emb) * cs, jnp.sin(emb) * cs
    rot = jnp.concatenate([-x[..., dim // 2:], x[..., :dim // 2]], axis=-1)
    return x * cos + rot * sin


def attention(q, k, v, scale, prec, block: int = 512):
    """Causal attention in query blocks of ``block`` rows.
    q/k (B, S, H, dq), v (B, S, H, dv)."""
    B, S, H, dq = q.shape
    block = min(block, S)
    nb = -(-S // block)
    qb = jnp.pad(q, ((0, 0), (0, nb * block - S), (0, 0), (0, 0)))
    qb = qb.reshape(B, nb, block, H, dq).swapaxes(0, 1)
    kpos = jnp.arange(S)
    if prec == "fp8":
        k, v = common.fp8_round(k), common.fp8_round(v)

    def one(args):
        qi, i = args
        if prec == "fp8":
            qi = common.fp8_round(qi)
        s = jnp.einsum("bqhd,bkhd->bhqk", qi, k,
                       precision=jax.lax.Precision.HIGHEST) * scale
        qpos = i * block + jnp.arange(block)
        s = jnp.where(qpos[:, None] >= kpos[None, :], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        if prec == "fp8":
            p = common.fp8_round(p)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v,
                          precision=jax.lax.Precision.HIGHEST)

    out = jax.lax.map(one, (qb, jnp.arange(nb)))           # (nb,B,blk,H,dv)
    return out.swapaxes(0, 1).reshape(B, nb * block, H, -1)[:, :S]


def mla(h, p, pos, cfg, prec):
    """Latent attention of one layer, keys and values expanded."""
    B, S, _ = h.shape
    H, r = cfg["n_heads"], cfg["kv_lora_rank"]
    nope, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                    cfg["v_head_dim"])
    inv, cs, scale = yarn(cfg)
    q = common.mm(h, p["attn/wq"], prec).reshape(B, S, H, nope + dr)
    ckv = common.mm(h, p["attn/wkv_a"], prec)
    c = common.rms_norm(ckv[..., :r], p["attn/kv_norm/norm"],
                        cfg["norm_eps"])
    kv = common.mm(c, p["attn/wkv_b"], prec).reshape(B, S, H, nope + dv)
    q_pe = rope(q[..., nope:], pos, inv, cs)
    k_pe = rope(ckv[..., None, r:], pos, inv, cs)
    qf = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
    kf = jnp.concatenate([kv[..., :nope],
                          jnp.broadcast_to(k_pe, (B, S, H, dr))], axis=-1)
    ctx = attention(qf, kf, kv[..., nope:], scale, prec)
    return common.mm(ctx.reshape(B, S, H * dv), p["attn/wo"], prec)


def moe(h, p, cfg, prec):
    """The routed part of the held experts, one expert at a time over
    every token (weight 0 where the token did not pick it), plus the
    shared experts."""
    B, S, d = h.shape
    x = h.reshape(B * S, d)
    probs = jax.nn.softmax(common.mm(x, p["moe/router"], prec), axis=-1)
    top_w, top_i = jax.lax.top_k(probs, cfg["experts_per_token"])

    def expert(y, e):
        g = jnp.sum(jnp.where(top_i == e, top_w, 0.0), axis=-1)
        out = common.swiglu(x, p["moe/wi"][e], p["moe/wo"][e], prec)
        return y + g[:, None] * out, None

    held = p["moe/wi"].shape[0]
    y, _ = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(held))
    y = y + common.swiglu(x, p["moe/shared_wi"], p["moe/shared_wo"], prec)
    return y.reshape(B, S, d)


def block(p, x, pos, cfg, prec, dense: bool):
    h = common.rms_norm(x, p["pre_norm"], cfg["norm_eps"])
    x = x + mla(h, p, pos, cfg, prec)
    h = common.rms_norm(x, p["mlp_norm"], cfg["norm_eps"])
    if dense:
        return x + common.swiglu(h, p["mlp/wi"], p["mlp/wo"], prec)
    return x + moe(h, p, cfg, prec)


def served_logits(cfg, seed: int, seqs: Sequence[np.ndarray],
                  at: Sequence[np.ndarray], prec: str = "f32",
                  pad_to: int = 512) -> List[np.ndarray]:
    """Logits rows (float32 numpy, ``len(at[i])`` x vocab) of sequence i
    at positions ``at[i]``; the sequences run as one batch, padded at
    the end to a multiple of ``pad_to`` (causal: padding is never seen
    by a real position)."""
    n = len(seqs)
    S = -(-max(len(s) for s in seqs) // pad_to) * pad_to
    toks = np.zeros((n, S), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    d, V = cfg["d_model"], cfg["vocab_size"]
    L, Ld = cfg["n_layers"], cfg["first_dense_layers"]
    top = lambda path, shape: weights.leaf_values(
        seed, path, shape, _dtype(path, cfg), L).astype(jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(S), (n, S))
    with jax.default_matmul_precision("highest"):
        x = jnp.take(top("embed", (V, d)), jnp.asarray(toks), axis=0)
        # weights are arguments, never closed over: a captured array
        # would be compiled into the program as a constant
        run_dense = jax.jit(lambda p, x: block(p, x, pos, cfg, prec, True))
        run_moe = jax.jit(lambda p, x: block(p, x, pos, cfg, prec, False))
        lead = {k: top(k, v) for k, v in dense_shapes(cfg).items()}
        for layer in range(Ld):
            p = {k.split("/", 1)[1]: v[layer] for k, v in lead.items()}
            x = run_dense(p, x)
        del lead
        shapes = moe_shapes(cfg)
        draw = jax.jit(lambda l: {
            k.split("/", 1)[1]: weights.layer_values(
                seed, k, l, v, _dtype(k, cfg), L)
            for k, v in shapes.items()})
        for layer in range(L - Ld):
            x = run_moe(draw(layer), x)
        fn = top("final_norm", (d,))
        head = top("lm_head", (d, V))
        rows = [x[i, jnp.asarray(a)] for i, a in enumerate(at)]
        out = jax.jit(lambda r, fn, head: common.mm(
            common.rms_norm(r, fn, cfg["norm_eps"]), head, prec))
        return [np.asarray(out(r, fn, head)) for r in rows]
