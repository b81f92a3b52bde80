"""GQA attention: training/prefill (chunked) and decode (cache read).

The chunked formulation scans MXU-aligned query blocks whose size comes
from the local-partitioning pass (``plan.partitions['flash_attention']``):
the same tile decision configures both this XLA-level path and the Pallas
kernel in :mod:`repro.kernels.flash_attention` — the paper's "the
datapath uses whatever the compiler configured" separation.

Peak live memory per block is ``block_q × seq`` scores instead of
``seq × seq``, which is what lets the 32k-prefill cells fit HBM.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.common import apply_rope, rms_norm

NEG_INF = -1e30


class AttnParams(NamedTuple):
    """Separate q/k/v projections: section boundaries of a fused QKV
    matmul rarely align with TP shard boundaries (e.g. (H+2K)·hd = 6144
    over 16 shards puts the q/k split mid-shard), and GSPMD then patches
    the `split` with collective-permute halos.  Split projections shard
    cleanly (layout-pass decision `qkv: split`)."""

    wq: jax.Array              # (d, H * hd)
    wk: jax.Array              # (d, K * hd)
    wv: jax.Array              # (d, K * hd)
    wo: jax.Array              # (H * hd, d)
    q_norm: Optional[jax.Array] = None   # (hd,) qwen3 qk-norm scales
    k_norm: Optional[jax.Array] = None


def _mask(
    q_pos: jax.Array,          # (..., Sq)
    k_pos: jax.Array,          # (..., Sk)
    causal: bool,
    window,                    # 0/None = unlimited; scalar or traced value
) -> jax.Array:
    m = jnp.ones(q_pos.shape[:-1] + (q_pos.shape[-1], k_pos.shape[-1]),
                 dtype=bool)
    diff = q_pos[..., :, None] - k_pos[..., None, :]
    if causal:
        m = m & (diff >= 0)
    if window is not None:
        w = jnp.asarray(window)
        m = m & jnp.where(w > 0, diff < w, True)
    return m


def gqa_scores(q: jax.Array, k: jax.Array) -> jax.Array:
    """q (B,Sq,H,hd) × k (B,Sk,K,hd) -> scores (B,K,G,Sq,Sk), G=H/K."""
    B, Sq, H, hd = q.shape
    K = k.shape[2]
    q = q.reshape(B, Sq, K, H // K, hd)
    return jnp.einsum("bqkgh,bskh->bkgqs", q, k,
                      preferred_element_type=jnp.float32)


def gqa_context(p: jax.Array, v: jax.Array) -> jax.Array:
    """p (B,K,G,Sq,Sk) × v (B,Sk,K,hd) -> (B,Sq,H,hd)."""
    B, K, G, Sq, Sk = p.shape
    ctx = jnp.einsum("bkgqs,bskh->bqkgh", p, v.astype(jnp.float32))
    return ctx.reshape(B, Sq, K * G, v.shape[-1])


def attention_chunked(
    q: jax.Array,              # (B, S, H, hd) — post-RoPE
    k: jax.Array,              # (B, S, K, hd)
    v: jax.Array,              # (B, S, K, hd)
    *,
    causal: bool,
    window=0,
    block_q: int = 512,
    positions: Optional[jax.Array] = None,   # (B, S)
) -> jax.Array:
    """Scan over query blocks; each block sees the full K/V stream.

    The per-block closure is rematerialized (``jax.checkpoint``) so the
    backward pass never holds more than one block's score matrix — the
    XLA equivalent of flash attention's O(S) memory.
    """
    B, S, H, hd = q.shape
    scale = hd ** -0.5
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    bq = min(block_q, S)
    n_blocks = -(-S // bq)
    pad = n_blocks * bq - S
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qpos_full = positions
    if pad:
        qpos_full = jnp.pad(positions, ((0, 0), (0, pad)),
                            constant_values=-1)

    q_blocks = q.reshape(B, n_blocks, bq, H, hd).transpose(1, 0, 2, 3, 4)
    qpos_blocks = qpos_full.reshape(B, n_blocks, bq).transpose(1, 0, 2)

    @functools.partial(jax.checkpoint, prevent_cse=False)
    def one_block(qb, qpb):
        s = gqa_scores(qb * scale, k)                     # (B,K,G,bq,S)
        m = _mask(qpb, positions, causal, window)          # (B,bq,S)
        m = m & (qpb >= 0)[..., :, None]
        s = jnp.where(m[:, None, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        return gqa_context(p, v).astype(q.dtype)          # (B,bq,H,hd)

    out = jax.lax.map(lambda xs: one_block(*xs), (q_blocks, qpos_blocks))
    out = out.transpose(1, 0, 2, 3, 4).reshape(B, n_blocks * bq, H, hd)
    return out[:, :S]


def attention_tail(
    q: jax.Array,              # (B, T, H, hd) — post-RoPE tail queries
    k: jax.Array,              # (B, M+T, K, hd) prefix ++ tail keys
    v: jax.Array,              # (B, M+T, K, hd)
    *,
    q_positions: jax.Array,    # (B, T) absolute positions of the tail
    k_positions: jax.Array,    # (B, M+T) absolute positions of all keys
    causal: bool,
    window=0,
    block_q: int = 512,
) -> jax.Array:
    """Chunked attention for a *tail* of queries over a longer key
    stream (prefix-cache prefill: the leading M keys come from resident
    pool blocks whose compute is being skipped).

    Deliberately mirrors :func:`attention_chunked` op-for-op — same
    ``gqa_scores`` einsum, same fp32 full-row softmax, same
    ``gqa_context`` contraction over the full key axis — so the tail
    positions' outputs are bitwise what a full-sequence prefill would
    have produced for them (token-identity across aliased vs private
    runs leans on this).
    """
    B, T, H, hd = q.shape
    scale = hd ** -0.5
    bq = min(block_q, T)
    n_blocks = -(-T // bq)
    pad = n_blocks * bq - T
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qpos_full = q_positions
    if pad:
        qpos_full = jnp.pad(q_positions, ((0, 0), (0, pad)),
                            constant_values=-1)
    q_blocks = q.reshape(B, n_blocks, bq, H, hd).transpose(1, 0, 2, 3, 4)
    qpos_blocks = qpos_full.reshape(B, n_blocks, bq).transpose(1, 0, 2)

    @functools.partial(jax.checkpoint, prevent_cse=False)
    def one_block(qb, qpb):
        s = gqa_scores(qb * scale, k)                     # (B,K,G,bq,M+T)
        m = _mask(qpb, k_positions, causal, window)       # (B,bq,M+T)
        m = m & (qpb >= 0)[..., :, None]
        s = jnp.where(m[:, None, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        return gqa_context(p, v).astype(q.dtype)          # (B,bq,H,hd)

    out = jax.lax.map(lambda xs: one_block(*xs), (q_blocks, qpos_blocks))
    out = out.transpose(1, 0, 2, 3, 4).reshape(B, n_blocks * bq, H, hd)
    return out[:, :T]


def attention_decode(
    q: jax.Array,              # (B, 1, H, hd) — post-RoPE
    k_cache: jax.Array,        # (B, S, K, hd)
    v_cache: jax.Array,        # (B, S, K, hd)
    *,
    cache_len: jax.Array,      # scalar or (B,): number of valid positions
    window=0,
) -> jax.Array:
    """One-token decode against the session cache (fp32 softmax)."""
    B, S, K, hd = k_cache.shape
    scale = hd ** -0.5
    s = gqa_scores(q * scale, k_cache)                    # (B,K,G,1,S)
    kpos = jnp.arange(S, dtype=jnp.int32)
    qpos = (jnp.asarray(cache_len) - 1).reshape(-1, 1)    # (B or 1, 1)
    valid = kpos[None, :] < jnp.asarray(cache_len).reshape(-1, 1)
    m = valid
    if window is not None:
        w = jnp.asarray(window)
        m = m & jnp.where(w > 0, (qpos - kpos[None, :]) < w, True)
    s = jnp.where(m[:, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return gqa_context(p, v_cache).astype(q.dtype)        # (B,1,H,hd)


def attention_decode_paged(
    q: jax.Array,              # (B, 1, H, hd) — post-RoPE
    k_pool: jax.Array,         # (N, bl, K, hd) block pool (one layer)
    v_pool: jax.Array,         # (N, bl, K, hd)
    block_tbl: jax.Array,      # (B, nb) block ids; -1 = unassigned
    *,
    cache_len: jax.Array,      # scalar or (B,): number of valid positions
    window=0,
) -> jax.Array:
    """One-token decode against a paged cache (XLA gather path).

    Gathers each slot's blocks into a dense per-slot view and reuses
    :func:`attention_decode`; positions past ``cache_len`` are masked, so
    stale pool rows (from a block's previous tenant) and the clamped
    block-0 read of unassigned entries never reach the softmax.  Oracle:
    :func:`repro.kernels.ref.paged_decode_attention_ref`.
    """
    N, bl = k_pool.shape[0], k_pool.shape[1]
    B, nb = block_tbl.shape
    safe = jnp.clip(block_tbl, 0, N - 1)
    with jax.named_scope("paged_gather"):
        k = k_pool[safe].reshape(B, nb * bl, *k_pool.shape[2:])
        v = v_pool[safe].reshape(B, nb * bl, *v_pool.shape[2:])
    return attention_decode(q, k, v, cache_len=cache_len, window=window)


def project_qkv(
    x: jax.Array,
    p: AttnParams,
    n_heads: int,
    n_kv: int,
    hd: int,
    positions: jax.Array,
    theta: float,
    mrope_sections=None,
    qk_norm_eps: float = 1e-6,
):
    q = (x @ p.wq).reshape(*x.shape[:-1], n_heads, hd)
    k = (x @ p.wk).reshape(*x.shape[:-1], n_kv, hd)
    v = (x @ p.wv).reshape(*x.shape[:-1], n_kv, hd)
    if p.q_norm is not None:
        q = rms_norm(q, p.q_norm, qk_norm_eps)
        k = rms_norm(k, p.k_norm, qk_norm_eps)
    q = apply_rope(q, positions, theta, mrope_sections)
    k = apply_rope(k, positions, theta, mrope_sections)
    return q, k, v
