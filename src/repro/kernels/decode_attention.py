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
    fetch_ref,    # SMEM (B, nb) int32 pool block each step reads (scalar prefetch)
    live_ref,     # SMEM (B, nb) int32 1 where the step's block is live
    len_ref,      # SMEM (B,) int32 per-slot valid lengths
    layer_ref,    # SMEM (1,) int32 layer of the stacked pools
    win_ref,      # SMEM (1,) int32 attention window, 0 = full
    q_ref,        # (1, K, G, D)
    k_ref,        # (1, 1, block_len, K, D) — the step's block, all kv heads
    v_ref,        # (1, 1, block_len, K, D)
    o_ref,        # (1, K, G, D)
    m_scr, l_scr, acc_scr,      # (K, G), (K, G), (K, G, D)
    *,
    block_len: int,
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
    window = win_ref[0]

    @pl.when(live_ref[b, j] != 0)
    def _block():
        def seen(shape, dim):
            kpos = j * block_len + jax.lax.broadcasted_iota(
                jnp.int32, shape, dim)
            return (kpos < cache_len) & (
                (window <= 0) | ((cache_len - 1 - kpos) < window))

        s_mask = seen((1, block_len), 1)
        v_mask = seen((block_len, 1), 0)
        for h in range(q_ref.shape[1]):
            q = q_ref[0, h] * jnp.asarray(scale, q_ref.dtype)  # (G, D)
            k = k_ref[0, 0, :, h]                               # (bl, D)
            # rows past cache_len hold a block's stale or unwritten
            # rows: zero them, so a non-finite one never meets p == 0
            v = jnp.where(v_mask, v_ref[0, 0, :, h], 0).astype(jnp.float32)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(s_mask, s, NEG_INF)
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


def _fetch_plan(tbl, cache_len, window, block_len):
    """Which table entries the decode reads, and the block each grid
    step fetches.

    An entry is live when it is assigned and holds a row the query sees:
    ``[cache_len - window, cache_len)``, or all of ``[0, cache_len)``
    when ``window`` is 0.  A step on a dead entry names the block the
    last live step before it fetched (the first live block, before any),
    so Pallas, which skips the copy of a block index that has not
    changed, issues one DMA per live block and none for the dead steps:
    the tail past a slot's length, the columns before its window, and a
    free slot's row."""
    B, nb = tbl.shape
    start = jnp.arange(nb, dtype=jnp.int32) * block_len
    lo = jnp.where(window > 0, cache_len - window, 0)[:, None]
    live = (start < cache_len[:, None]) & (start + block_len > lo) \
        & (tbl >= 0)
    step = jnp.arange(B * nb, dtype=jnp.int32)
    flat = live.reshape(-1)
    last = jax.lax.cummax(jnp.where(flat, step, -1))
    src = jnp.where(last >= 0, last, jnp.argmax(flat))
    fetch = jnp.maximum(tbl.reshape(-1)[src], 0).reshape(B, nb)
    return fetch, live.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(
    q: jax.Array,              # (B, H, D)
    k_pool: jax.Array,         # (L, N, bl, K, D) every layer's pool
    v_pool: jax.Array,         # (L, N, bl, K, D)
    block_tbl: jax.Array,      # (B, nb) int32 block ids (-1 = unassigned)
    *,
    cache_len: jax.Array,      # (B,) or scalar int32 valid lengths
    window=0,                  # int or int32 scalar; 0 = full attention
    layer=0,                   # int or int32 scalar: the layer to read
    interpret: bool = False,
) -> jax.Array:
    """Flash-decode over a paged cache: each slot's live blocks stream
    through VMEM via scalar-prefetch indexed DMA.

    The grid walks (batch, table_cols); each step's k/v BlockSpec index
    map reads the block to fetch from a table prefetched to SMEM before
    the kernel runs (:func:`_fetch_plan`), all kv heads at once, from
    ``layer`` of a stacked pool where it lies: neither the layer nor a
    dense per-slot view is ever copied out in HBM.  Only live steps
    compute; the others neither fetch nor compute.  A free slot (table
    row all -1) reads as zeros, like an unassigned block in the oracle,
    :func:`repro.kernels.ref.paged_decode_attention_ref`, whose
    semantics this matches.
    """
    B, H, D = q.shape
    block_len, K = k_pool.shape[2], k_pool.shape[3]
    nb = block_tbl.shape[1]
    G = H // K
    scale = D ** -0.5

    qg = q.reshape(B, K, G, D)
    clen = jnp.broadcast_to(
        jnp.asarray(cache_len, jnp.int32).reshape(-1), (B,))
    win = jnp.asarray(window, jnp.int32)
    fetch, live = _fetch_plan(jnp.asarray(block_tbl, jnp.int32), clen, win,
                              block_len)

    def kv_index(b, j, fetch_ref, live_ref, len_ref, layer_ref, win_ref):
        return (layer_ref[0], fetch_ref[b, j], 0, 0, 0)

    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, block_len=block_len,
                          scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(B, nb),
            in_specs=[
                pl.BlockSpec((1, K, G, D), lambda b, j, *_: (b, 0, 0, 0)),
                pl.BlockSpec((1, 1, block_len, K, D), kv_index),
                pl.BlockSpec((1, 1, block_len, K, D), kv_index),
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
    )(fetch, live, clen, jnp.asarray(layer, jnp.int32).reshape(1),
      win.reshape(1), qg, k_pool, v_pool)
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
