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

from repro.kernels.decode_attention import paged_decode_attention
from repro.models.common import (apply_rope, mla_softmax_scale, rms_norm,
                                 rope_interleaved, yarn_rope_table)

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
    scale: Optional[float] = None,           # None -> hd ** -0.5
) -> jax.Array:
    """Scan over query blocks; each block sees the full K/V stream.

    The per-block closure is rematerialized (``jax.checkpoint``) so the
    backward pass never holds more than one block's score matrix — the
    XLA equivalent of flash attention's O(S) memory.  Values may be
    narrower than queries and keys (MLA: 192 against 128).
    """
    B, S, H, hd = q.shape
    dv = v.shape[-1]
    scale = hd ** -0.5 if scale is None else scale
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
        return gqa_context(p, v).astype(q.dtype)          # (B,bq,H,dv)

    out = jax.lax.map(lambda xs: one_block(*xs), (q_blocks, qpos_blocks))
    out = out.transpose(1, 0, 2, 3, 4).reshape(B, n_blocks * bq, H, dv)
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
    scale: Optional[float] = None,
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
    dv = v.shape[-1]
    scale = hd ** -0.5 if scale is None else scale
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
        return gqa_context(p, v).astype(q.dtype)          # (B,bq,H,dv)

    out = jax.lax.map(lambda xs: one_block(*xs), (q_blocks, qpos_blocks))
    out = out.transpose(1, 0, 2, 3, 4).reshape(B, n_blocks * bq, H, dv)
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


def attention_decode_paged_stacked(
    q: jax.Array,              # (B, 1, H, hd) — post-RoPE
    k_pools: jax.Array,        # (L, N, bl, K, hd) every layer's pool
    v_pools: jax.Array,        # (L, N, bl, K, hd)
    block_tbl: jax.Array,      # (B, nb) block ids; -1 = unassigned
    *,
    layer: jax.Array,          # int32 scalar: the layer to read
    cache_len: jax.Array,      # (B,): number of valid positions
    window=0,
) -> jax.Array:
    """One-token decode against layer ``layer`` of the stacked paged
    pools (Pallas path): the kernel streams only each slot's live
    blocks, from where they lie in the stacked pool.  Lowered for
    another platform than a TPU, it runs in interpret mode.  Oracle:
    :func:`attention_decode_paged` of the layer's pools."""
    def run(interpret):
        return functools.partial(
            paged_decode_attention, cache_len=cache_len, window=window,
            layer=layer, interpret=interpret)

    ctx = jax.lax.platform_dependent(
        q[:, 0], k_pools, v_pools, block_tbl,
        tpu=run(False), default=run(True))
    return ctx[:, None]


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


# ---------------------------------------------------------------------
# Latent attention (MLA, deepseek-v2)
# ---------------------------------------------------------------------

class MLAParams(NamedTuple):
    """Multi-head latent attention without a query LoRA."""

    wq: jax.Array              # (d, H * (nope + rope))
    wkv_a: jax.Array           # (d, r + rope): latent c_kv, shared k_pe
    kv_norm: jax.Array         # (r,) RMSNorm on c_kv (offset from 1)
    wkv_b: jax.Array           # (r, H * (nope + v))
    wo: jax.Array              # (H * v, d)


def mla_project(x: jax.Array, p: MLAParams, arch, positions: jax.Array):
    """Queries and the cache row of each token.

    Returns ``q_nope`` (B,S,H,nope), ``q_pe`` (B,S,H,rope) rotated, and
    the latent ``row`` (B,S,r+rope): the normalized ``c_kv`` followed by
    the rotated shared ``k_pe`` — all a token leaves in the cache."""
    nope, rope = arch.qk_nope_head_dim, arch.qk_rope_head_dim
    r = arch.kv_lora_rank
    q = (x @ p.wq).reshape(*x.shape[:-1], -1, nope + rope)
    q_nope, q_pe = q[..., :nope], q[..., nope:]
    ckv = x @ p.wkv_a
    c = rms_norm(ckv[..., :r], p.kv_norm, arch.norm_eps)
    inv, cs = yarn_rope_table(arch)
    q_pe = rope_interleaved(q_pe, positions, inv, cs)
    k_pe = rope_interleaved(ckv[..., None, r:], positions, inv, cs)[..., 0, :]
    return q_nope, q_pe, jnp.concatenate([c, k_pe.astype(c.dtype)], axis=-1)


def mla_expand(row: jax.Array, p: MLAParams, arch):
    """Per-head keys (B,S,H,nope+rope) and values (B,S,H,v) expanded
    from latent rows (B,S,r+rope): the prefill form."""
    nope, r = arch.qk_nope_head_dim, arch.kv_lora_rank
    kv = (row[..., :r] @ p.wkv_b).reshape(*row.shape[:-1], -1,
                                           nope + arch.v_head_dim)
    k_pe = jnp.broadcast_to(row[..., None, r:],
                            (*kv.shape[:-1], arch.qk_rope_head_dim))
    return (jnp.concatenate([kv[..., :nope], k_pe], axis=-1),
            kv[..., nope:])


def attention_decode_latent(
    q_nope: jax.Array,         # (B, 1, H, nope)
    q_pe: jax.Array,           # (B, 1, H, rope) — rotated
    rows: jax.Array,           # (B, S, r + rope) latent cache rows
    p: MLAParams,
    arch,
    *,
    cache_len: jax.Array,      # (B,) valid positions
) -> jax.Array:
    """One-token decode in the absorbed form: ``W_UK`` is folded into
    the query and ``W_UV`` applied after the context, so every head
    attends over the one shared latent row (MQA of H heads over a
    width of r + rope).  Returns (B, 1, H, v)."""
    nope, r = arch.qk_nope_head_dim, arch.kv_lora_rank
    B, S, _ = rows.shape
    wkv_b = p.wkv_b.reshape(r, -1, nope + arch.v_head_dim)
    with jax.named_scope("absorb"):
        q_lat = jnp.einsum("bqhn,rhn->bqhr", q_nope, wkv_b[..., :nope],
                           preferred_element_type=jnp.float32)
        q = jnp.concatenate([q_lat.astype(rows.dtype),
                             q_pe.astype(rows.dtype)], axis=-1)
    q = q * jnp.asarray(mla_softmax_scale(arch), q.dtype)
    s = jnp.einsum("bqhc,bsc->bhqs", q, rows,
                   preferred_element_type=jnp.float32)
    valid = jnp.arange(S, dtype=jnp.int32)[None, :] < \
        jnp.asarray(cache_len).reshape(-1, 1)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    pr = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bhqs,bsr->bqhr", pr, rows[..., :r].astype(jnp.float32))
    with jax.named_scope("absorb"):
        out = jnp.einsum("bqhr,rhv->bqhv", ctx.astype(rows.dtype),
                         wkv_b[..., nope:],
                         preferred_element_type=jnp.float32)
    return out.astype(rows.dtype)


def attention_decode_latent_paged(q_nope, q_pe, pool, block_tbl, p, arch,
                                  *, cache_len):
    """:func:`attention_decode_latent` over a paged latent pool
    ``(N, bl, r + rope)`` (one layer): each slot's blocks are gathered
    once into a dense view; rows past ``cache_len`` are masked."""
    N, bl = pool.shape[0], pool.shape[1]
    B, nb = block_tbl.shape
    safe = jnp.clip(block_tbl, 0, N - 1)
    with jax.named_scope("paged_gather"):
        rows = pool[safe].reshape(B, nb * bl, pool.shape[-1])
    return attention_decode_latent(q_nope, q_pe, rows, p, arch,
                                   cache_len=cache_len)
