"""GQA flash-decode Pallas TPU kernel: one query token vs the session cache.

The session cache streams through VMEM in ``block_kv``-row banks (the
``cache.kv`` template component configured by the local-partitioning
pass); the online-softmax carry stays in VMEM scratch.  Decode is
memory-bound — the kernel's job is to stream the cache exactly once at
full HBM bandwidth with no score materialization.

Grid: (batch, kv_head, cache_blocks).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(
    len_ref,      # SMEM (1,) int32: valid cache length
    q_ref,        # (1, 1, G, D)
    k_ref,        # (1, block_kv, 1, D)
    v_ref,        # (1, block_kv, 1, D)
    o_ref,        # (1, 1, G, D)
    m_scr, l_scr, acc_scr,
    *,
    block_kv: int,
    window: int,
    scale: float,
):
    j = pl.program_id(2)
    G, D = q_ref.shape[2], q_ref.shape[3]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    cache_len = len_ref[0]
    q = q_ref[0, 0].astype(jnp.float32) * scale          # (G, D)
    k = k_ref[0, :, 0].astype(jnp.float32)               # (block_kv, D)
    v = v_ref[0, :, 0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (G, bkv)
    kpos = j * block_kv + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_kv), 1)[0]
    mask = kpos < cache_len
    if window > 0:
        mask &= (cache_len - 1 - kpos) < window
    s = jnp.where(mask[None, :], s, NEG_INF)

    m_prev = m_scr[...]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur[:, None])
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1)
    acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_cur

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        denom = jnp.maximum(l_scr[...], 1e-30)[:, None]
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def _paged_decode_kernel(
    tbl_ref,      # SMEM (B, nb) int32 block table (scalar prefetch)
    len_ref,      # SMEM (B,) int32 per-slot valid lengths (scalar prefetch)
    q_ref,        # (1, K, G, D)
    k_ref,        # (1, block_len, K, D) — the slot's j-th block, all kv heads
    v_ref,        # (1, block_len, K, D)
    o_ref,        # (1, K, G, D)
    m_scr, l_scr, acc_scr,      # (K, G), (K, G), (K, G, D)
    *,
    block_len: int,
    window: int,
    scale: float,
):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    cache_len = len_ref[b]
    # dense-view positions: block j covers rows [j*bl, (j+1)*bl); an
    # unassigned (-1) table entry was clamped to block 0 by the index
    # map, but its whole range sits past cache_len, so the mask kills it
    kpos = j * block_len + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_len), 1)[0]
    mask = kpos < cache_len
    if window > 0:
        mask &= (cache_len - 1 - kpos) < window

    for h in range(q_ref.shape[1]):
        q = q_ref[0, h].astype(jnp.float32) * scale      # (G, D)
        k = k_ref[0, :, h].astype(jnp.float32)           # (block_len, D)
        v = v_ref[0, :, h].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = jnp.where(mask[None, :], s, NEG_INF)
        m_prev = m_scr[h]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, None])
        l_scr[h] = l_scr[h] * alpha + jnp.sum(p, axis=-1)
        acc_scr[h] = acc_scr[h] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[h] = m_cur

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        denom = jnp.maximum(l_scr[...], 1e-30)[..., None]
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_decode_attention(
    q: jax.Array,              # (B, H, D)
    k_pool: jax.Array,         # (N, block_len, K, D) block pool
    v_pool: jax.Array,         # (N, block_len, K, D)
    block_tbl: jax.Array,      # (B, nb) int32 block ids (-1 = unassigned)
    *,
    cache_len: jax.Array,      # (B,) or scalar int32 valid lengths
    window: int = 0,
    interpret: bool = False,
) -> jax.Array:
    """Flash-decode over a paged cache: the block table streams the
    slot's blocks through VMEM via scalar-prefetch indexed DMA.

    The grid walks (batch, table_cols); each step's k/v BlockSpec index
    map reads ``block_tbl[b, j]`` (prefetched to SMEM before the kernel
    runs) to pick the pool block to DMA, all kv heads at once — the gather
    never materializes a dense per-slot cache in HBM.  Semantics match
    :func:`repro.kernels.ref.paged_decode_attention_ref`.
    """
    B, H, D = q.shape
    N, block_len, K = k_pool.shape[0], k_pool.shape[1], k_pool.shape[2]
    nb = block_tbl.shape[1]
    G = H // K
    scale = D ** -0.5

    qg = q.reshape(B, K, G, D)
    clen = jnp.broadcast_to(
        jnp.asarray(cache_len, jnp.int32).reshape(-1), (B,))
    tbl = jnp.asarray(block_tbl, jnp.int32)

    def kv_index(b, j, tbl_ref, len_ref):
        # the pool is shared: the slot's table row picks the block; a
        # block carries every kv head, so one DMA per (slot, block)
        return (jnp.maximum(tbl_ref[b, j], 0), 0, 0, 0)

    grid = (B, nb)
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, block_len=block_len,
                          window=window, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, K, G, D), lambda b, j, *_: (b, 0, 0, 0)),
                pl.BlockSpec((1, block_len, K, D), kv_index),
                pl.BlockSpec((1, block_len, K, D), kv_index),
            ],
            out_specs=pl.BlockSpec((1, K, G, D),
                                   lambda b, j, *_: (b, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((K, G), jnp.float32),
                pltpu.VMEM((K, G), jnp.float32),
                pltpu.VMEM((K, G, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, K, G, D), q.dtype),
        interpret=interpret,
    )(tbl, clen, qg, k_pool, v_pool)
    return out.reshape(B, H, D)


@functools.partial(
    jax.jit, static_argnames=("window", "block_kv", "interpret"))
def decode_attention(
    q: jax.Array,              # (B, H, D)
    k: jax.Array,              # (B, S, K, D)
    v: jax.Array,              # (B, S, K, D)
    *,
    cache_len: jax.Array,      # scalar int32 (shared valid length)
    window: int = 0,
    block_kv: int = 2048,
    interpret: bool = False,
) -> jax.Array:
    B, H, D = q.shape
    S, K = k.shape[1], k.shape[2]
    G = H // K
    block_kv = min(block_kv, S)
    assert S % block_kv == 0, (S, block_kv)
    scale = D ** -0.5

    qg = q.reshape(B, 1, K, G, D).transpose(0, 2, 1, 3, 4) \
        .reshape(B * K, 1, G, D)
    kg = k.transpose(0, 2, 1, 3).reshape(B * K, S, 1, D)
    vg = v.transpose(0, 2, 1, 3).reshape(B * K, S, 1, D)
    clen = jnp.asarray(cache_len, jnp.int32).reshape(1)

    grid = (B * K, 1, S // block_kv)
    out = pl.pallas_call(
        functools.partial(_decode_kernel, block_kv=block_kv, window=window,
                          scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, G, D), lambda b, i, j: (b, 0, 0, 0)),
            pl.BlockSpec((1, block_kv, 1, D), lambda b, i, j: (b, j, 0, 0)),
            pl.BlockSpec((1, block_kv, 1, D), lambda b, i, j: (b, j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, i, j: (b, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B * K, 1, G, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
        interpret=interpret,
    )(clen, qg, kg, vg)
    return out.reshape(B, K, G, D).reshape(B, H, D)
