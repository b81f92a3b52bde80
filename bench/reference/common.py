"""Pieces of the plain references: float32 ``jax.numpy``, no kernels,
no cache, no batching tricks.  Nothing here imports the program.

``prec`` selects the arithmetic of every matrix product: ``"f32"`` is the
reference; ``"fp8"`` rounds both operands to float8 (e4m3, one scale per
tensor) first, the control that a lower precision than the
configuration's bfloat16 has to fail.
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

import weights

F8_MAX = 448.0          # largest finite float8_e4m3fn


def fp8_round(x: jax.Array) -> jax.Array:
    """Round to float8 e4m3 with one scale per tensor, back to float32."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm(a: jax.Array, b: jax.Array, prec: str) -> jax.Array:
    if prec == "fp8":
        a, b = fp8_round(a), fp8_round(b)
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    """RMSNorm with the scale stored as an offset from 1."""
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


def rope(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding over the last axis, halves rotated together.
    x (..., S, H, hd), pos (..., S)."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[..., None, None] * inv
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)


def attention(q: jax.Array, k: jax.Array, v: jax.Array, window,
              prec: str, block: int = 512) -> jax.Array:
    """Causal grouped-query attention, optionally windowed (``window`` 0
    is unlimited: key j is seen from query i when 0 <= i - j < window).
    q (B, S, H, hd), k/v (B, S, K, hd).  Query blocks of ``block`` rows,
    each recomputed in the backward pass, bound the score memory."""
    B, S, H, hd = q.shape
    K = k.shape[2]
    G = H // K
    block = min(block, S)
    nb = -(-S // block)
    pad = nb * block - S
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qb = qp.reshape(B, nb, block, K, G, hd).transpose(1, 0, 2, 3, 4, 5)
    kpos = jnp.arange(S)
    if prec == "fp8":
        k, v = fp8_round(k), fp8_round(v)

    @jax.checkpoint
    def one(args):
        qi, i = args
        if prec == "fp8":
            qi = fp8_round(qi)
        s = jnp.einsum("bqkgd,bskd->bkgqs", qi, k,
                       precision=jax.lax.Precision.HIGHEST) / math.sqrt(hd)
        qpos = i * block + jnp.arange(block)
        diff = qpos[:, None] - kpos[None, :]
        ok = diff >= 0
        ok = ok & jnp.where(window > 0, diff < window, True)
        s = jnp.where(ok, s, -1e30)     # finite: padded rows stay NaN-free
        p = jax.nn.softmax(s, axis=-1)
        if prec == "fp8":
            p = fp8_round(p)
        return jnp.einsum("bkgqs,bskd->bqkgd", p, v,
                          precision=jax.lax.Precision.HIGHEST)

    out = jax.lax.map(one, (qb, jnp.arange(nb)))      # (nb,B,blk,K,G,hd)
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(B, nb * block, H, hd)
    return out[:, :S]


def swiglu(x: jax.Array, wi: jax.Array, wo: jax.Array, prec: str):
    gu = mm(x, wi, prec)
    g, u = jnp.split(gu, 2, axis=-1)
    return mm(jax.nn.silu(g) * u, wo, prec)


def gqa_block(h, p: Dict[str, jax.Array], pos, cfg, window, prec):
    """Attention of one layer: projections, optional qk-norm, rotary
    embedding, attention, output projection.  h (B, S, d)."""
    B, S, _ = h.shape
    H, K, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    q = mm(h, p["wq"], prec).reshape(B, S, H, hd)
    k = mm(h, p["wk"], prec).reshape(B, S, K, hd)
    v = mm(h, p["wv"], prec).reshape(B, S, K, hd)
    if "q_norm" in p:
        q = rms_norm(q, p["q_norm"], cfg["norm_eps"])
        k = rms_norm(k, p["k_norm"], cfg["norm_eps"])
    q = rope(q, pos, cfg["rope_theta"])
    k = rope(k, pos, cfg["rope_theta"])
    ctx = attention(q, k, v, window, prec)
    return mm(ctx.reshape(B, S, H * hd), p["wo"], prec)


def param_dtype(path: str, cfg) -> jnp.dtype:
    """The dtype the configuration stores a leaf in: float32 for norm
    scales and the SSM's per-head scalars, else ``cfg["dtype"]``."""
    name = path.rsplit("/", 1)[-1]
    if name in weights._NORMS or name in ("A_log", "D", "dt_bias"):
        return jnp.dtype("float32")
    return jnp.dtype(cfg["dtype"])


def draw_layer(seed: int, cfg, shapes: Dict[str, tuple], layer: int):
    """One layer's leaves ``{path: float32 array}``; ``shapes`` maps each
    stacked path to its per-layer shape."""
    return {path: weights.layer_values(seed, path, layer, shp,
                                       param_dtype(path, cfg),
                                       cfg["n_layers"])
            for path, shp in shapes.items()}


def draw_top(seed: int, cfg, path: str, shape: tuple) -> jax.Array:
    return weights.leaf_values(seed, path, shape, param_dtype(path, cfg),
                               cfg["n_layers"]).astype(jnp.float32)

