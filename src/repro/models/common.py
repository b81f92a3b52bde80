"""Shared model components: norms, rotary embeddings, initializers.

All computations follow the numerics decided by the layout pass: bf16
streams, fp32 for norms/softmax/rotary tables.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(dtype)


def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    """(head_dim/2,) inverse frequencies, fp32."""
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(
    x: jax.Array,                 # (..., seq, heads, head_dim)
    positions: jax.Array,         # (..., seq) int32
    theta: float,
    mrope_sections: Optional[Tuple[int, int, int]] = None,
) -> jax.Array:
    """Rotary embedding; supports qwen2-vl M-RoPE via 3 position streams.

    With M-RoPE, ``positions`` has shape (3, ..., seq): temporal / height /
    width ids.  The hd/2 frequency slots are split into the configured
    sections, each rotated by its own position stream.
    """
    if theta <= 0:
        return x
    hd = x.shape[-1]
    half = hd // 2
    inv = rope_frequencies(hd, theta)                       # (half,)
    if mrope_sections is not None:
        sec = np.cumsum((0,) + tuple(mrope_sections))
        assert sec[-1] == half, (mrope_sections, half)
        # pick which of the 3 position streams drives each frequency slot
        sel = np.zeros((half,), dtype=np.int32)
        for i in range(3):
            sel[sec[i]:sec[i + 1]] = i
        pos = positions.astype(jnp.float32)                 # (3, ..., seq)
        pos = jnp.moveaxis(pos, 0, -1)                      # (..., seq, 3)
        angles = pos[..., sel] * inv                        # (..., seq, half)
    else:
        angles = positions.astype(jnp.float32)[..., None] * inv
    # broadcast over heads: x is (..., seq, heads, hd)
    angles = angles[..., None, :]                           # (..., seq, 1, half)
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def yarn_rope_table(arch) -> Tuple[np.ndarray, float]:
    """Inverse frequencies (rope/2,) of the rotary dims and the scale of
    their cos/sin, YaRN-interpolated when ``arch.yarn_factor`` is set
    (deepseek-v2's ``DeepseekV2YarnRotaryEmbedding``)."""
    dim, base = arch.qk_rope_head_dim, arch.rope_theta
    exps = np.arange(0, dim, 2, dtype=np.float64) / dim
    extra = 1.0 / base ** exps
    f = arch.yarn_factor
    if not f:
        return extra.astype(np.float32), 1.0

    def corr(rot):
        return (dim * math.log(arch.yarn_original_len / (rot * 2 * math.pi))
                / (2 * math.log(base)))

    lo = max(math.floor(corr(arch.yarn_beta_fast)), 0)
    hi = min(math.ceil(corr(arch.yarn_beta_slow)), dim - 1)
    if lo == hi:
        hi += 0.001
    ramp = np.clip((np.arange(dim // 2) - lo) / (hi - lo), 0.0, 1.0)
    keep = 1.0 - ramp                  # 1: extrapolate (high frequency)
    inv = extra / f * (1.0 - keep) + extra * keep
    cs = yarn_mscale(f, arch.yarn_mscale) / yarn_mscale(
        f, arch.yarn_mscale_all_dim)
    return inv.astype(np.float32), float(cs)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 or not mscale else \
        0.1 * mscale * math.log(factor) + 1.0


def mla_softmax_scale(arch) -> float:
    """(nope + rope)^-0.5, times YaRN's attention temperature squared."""
    s = (arch.qk_nope_head_dim + arch.qk_rope_head_dim) ** -0.5
    if arch.yarn_factor and arch.yarn_mscale_all_dim:
        s *= yarn_mscale(arch.yarn_factor, arch.yarn_mscale_all_dim) ** 2
    return s


def rope_interleaved(x: jax.Array, positions: jax.Array, inv: np.ndarray,
                     cs: float = 1.0) -> jax.Array:
    """Rotary embedding of dims read in interleaved pairs, as deepseek-v2
    reads them: pair (x[2i], x[2i+1]) turns by ``pos * inv[i]``; the
    result is laid out de-interleaved (evens, then odds).
    x (..., seq, heads, dim), positions (..., seq)."""
    ang = positions.astype(jnp.float32)[..., None, None] * jnp.asarray(inv)
    c, s = jnp.cos(ang) * cs, jnp.sin(ang) * cs
    x1 = x[..., 0::2].astype(jnp.float32)
    x2 = x[..., 1::2].astype(jnp.float32)
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(seq: int, dim: int) -> jax.Array:
    """Fixed sinusoidal position table (hubert frontend stub)."""
    pos = np.arange(seq)[:, None]
    i = np.arange(dim)[None, :]
    angle = pos / np.power(10000.0, 2 * (i // 2) / dim)
    table = np.where(i % 2 == 0, np.sin(angle), np.cos(angle))
    return jnp.asarray(table, dtype=jnp.float32)


def truncated_normal_init(key: jax.Array, shape, dtype, scale: float = 0.02):
    return (scale * jax.random.truncated_normal(key, -2.0, 2.0, shape,
                                                jnp.float32)).astype(dtype)


def cross_entropy_loss(
    logits: jax.Array,            # (..., vocab) any float dtype
    targets: jax.Array,           # (...,) int32
    mask: Optional[jax.Array] = None,
    z_loss: float = 1e-4,
    vocab_size: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Masked mean CE with z-loss; handles padded vocab via vocab_size.

    Sharding-friendly formulation: no gather over the (model-sharded)
    vocab dim — the target log-prob comes from a fused one-hot reduction
    and the padded-vocab mask is a fused iota compare, so the full-vocab
    logits are never re-laid-out or gathered (they would be 40 GiB/device
    for a 150k vocab at 16x4096 tokens).
    """
    v = logits.shape[-1]
    logits = logits.astype(jnp.float32)
    if vocab_size is not None and vocab_size < v:
        # padded slots -> -inf via fused iota-compare (never materialized)
        slot = jax.lax.broadcasted_iota(jnp.int32, (v,), 0)
        logits = jnp.where(slot < vocab_size, logits, -1e30)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    onehot = (jax.lax.broadcasted_iota(jnp.int32, (v,), 0)
              == targets[..., None])
    ll = jnp.sum(jnp.where(onehot, logits, 0.0), axis=-1)
    nll = lse - ll
    if z_loss:
        nll = nll + z_loss * jnp.square(lse)
    if mask is not None:
        mask = mask.astype(jnp.float32)
        n = jnp.maximum(mask.sum(), 1.0)
        return (nll * mask).sum() / n, n
    n = jnp.asarray(nll.size, jnp.float32)
    return nll.mean(), n
