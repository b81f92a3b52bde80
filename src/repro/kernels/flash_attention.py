"""Flash attention (forward) Pallas TPU kernel.

PLM mapping (paper §3 → DESIGN.md §2): the q/k/v/o tiles are the
multi-bank PLM; block sizes come from the local-partitioning pass
(``plan.partitions['flash_attention']``), chosen so the double-buffered
working set fits the VMEM budget and tile dims are MXU multiples.

Grid: (batch·kv_head, q_blocks, kv_blocks) — kv innermost so the online
softmax carry (m, l, acc) lives in VMEM scratch across kv steps.
GQA is handled by loading q as (G, block_q, D) per kv head and looping
over the G query heads inside the kernel.

Causal grid pruning: with ``causal=True`` the kv blocks strictly above
the diagonal are fully masked, so computing-then-masking them wastes
~half the grid at long S.  Pallas TPU grids are rectangular, so the
pruned path packs the lower triangle by *pairing* q rows: row ``i`` (has
``i+1`` valid kv blocks) shares a grid row with row ``n-1-i`` (has
``n-i``), giving a rectangle of ``ceil(n/2) x (n+1)`` steps instead of
``n^2`` — a ``(n+1)/2n -> 1/2`` step ratio, with bit-identical output
(the skipped blocks contribute exactly-zero terms to the online
softmax).  The packing needs square tiles, so it engages only when
``block_q == block_kv`` — the partitioning pass emits square tiles for
causal workloads; rectangular tile choices keep the full grid.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def kv_grid_steps(seq_len: int, block_q: int, block_kv: int, *,
                  causal: bool = True, prune: bool = True) -> int:
    """(q, kv) grid steps per (batch x kv_head) the kernel launches.

    The pruning acceptance math: for the packed causal grid (square
    tiles only) the ratio to the unpruned ``n^2`` grid is ``(n+1)/2n``
    (→ 1/2 for large ``n``).
    """
    if causal and prune and block_q == block_kv:
        n = seq_len // block_q
        return ((n + 1) // 2) * (n + 1)
    return (seq_len // block_q) * (seq_len // block_kv)


def _mask_scores(s, q_idx, kv_idx, block_q, block_kv, causal, window):
    qpos = q_idx * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 0)
    kpos = kv_idx * block_kv + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 1)
    mask = jnp.ones((block_q, block_kv), dtype=jnp.bool_)
    if causal:
        mask &= qpos >= kpos
    if window > 0:
        mask &= (qpos - kpos) < window
    return jnp.where(mask, s, NEG_INF)


def _init_carry(m_scr, l_scr, acc_scr):
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)


def _online_step(q_ref, k_ref, v_ref, m_scr, l_scr, acc_scr, q_idx, kv_idx,
                 *, block_q, block_kv, causal, window, scale):
    """One (q tile, kv tile) online-softmax update for each of the G
    query heads sharing this kv head.  Every operand is a 2-D
    (rows, D) tile, so no in-kernel reshape crosses the (8, 128)
    register tiling."""
    k = k_ref[0].astype(jnp.float32)                      # (block_kv, D)
    v = v_ref[0].astype(jnp.float32)

    # a loop, not an unrolled comprehension: one head's (block_q,
    # block_kv) scores live at a time, which bounds VMEM at any G
    def head(g, carry):
        q = q_ref[0, g].astype(jnp.float32) * scale       # (block_q, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = _mask_scores(s, q_idx, kv_idx, block_q, block_kv, causal,
                         window)
        m_prev = m_scr[g]                                 # (block_q, 1)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)
        l_scr[g] = l_scr[g] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[g] = acc_scr[g] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[g] = m_cur
        return carry

    jax.lax.fori_loop(0, q_ref.shape[1], head, 0)


def _write_out(o_ref, l_scr, acc_scr):
    denom = jnp.maximum(l_scr[...], 1e-30)
    o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def _flash_kernel(
    # refs sliced by BlockSpec:
    q_ref,        # (1, G, block_q, D)
    k_ref,        # (1, block_kv, D)
    v_ref,        # (1, block_kv, D)
    o_ref,        # (1, G, block_q, D)
    m_scr, l_scr, acc_scr,      # VMEM scratch: (G, block_q, 1) x2, (G, block_q, D)
    *,
    causal: bool,
    window: int,
    block_q: int,
    block_kv: int,
    scale: float,
):
    kv_idx = pl.program_id(2)
    q_idx = pl.program_id(1)

    @pl.when(kv_idx == 0)
    def _init():
        _init_carry(m_scr, l_scr, acc_scr)

    _online_step(q_ref, k_ref, v_ref, m_scr, l_scr, acc_scr, q_idx, kv_idx,
                 block_q=block_q, block_kv=block_kv, causal=causal,
                 window=window, scale=scale)

    @pl.when(kv_idx == pl.num_programs(2) - 1)
    def _finish():
        _write_out(o_ref, l_scr, acc_scr)


# ---------------------------------------------------------------------
# packed-causal grid: q rows i and n-1-i share one grid row
# ---------------------------------------------------------------------

def _packed_coords(r, c, n):
    """Grid (r, c) -> (q block i, kv block j, segment flags).

    Row pair ``r``: columns ``[0, r]`` walk q row ``r`` (kv j = c);
    columns ``[r+1, n]`` walk q row ``n-1-r`` (kv j = c - r - 1).  For
    odd ``n`` the middle row pairs with itself — its second segment is
    dead and must be skipped (``valid`` False).
    """
    seg2 = c > r
    i = jnp.where(seg2, n - 1 - r, r)
    j = jnp.where(seg2, c - r - 1, c)
    valid = jnp.logical_or(jnp.logical_not(seg2), (n - 1 - r) != r)
    seg_start = jnp.logical_or(c == 0, c == r + 1)
    seg_end = jnp.where(seg2, c == n, c == r)
    return i, j, valid, seg_start, seg_end


def _flash_kernel_packed(
    q_ref, k_ref, v_ref, o_ref,
    m_scr, l_scr, acc_scr,
    *,
    window: int,
    block: int,
    n: int,
    scale: float,
):
    r = pl.program_id(1)
    c = pl.program_id(2)
    i, j, valid, seg_start, seg_end = _packed_coords(r, c, n)

    @pl.when(valid & seg_start)
    def _init():
        _init_carry(m_scr, l_scr, acc_scr)

    @pl.when(valid)
    def _compute():
        _online_step(q_ref, k_ref, v_ref, m_scr, l_scr, acc_scr, i, j,
                     block_q=block, block_kv=block, causal=True,
                     window=window, scale=scale)

    @pl.when(valid & seg_end)
    def _finish():
        _write_out(o_ref, l_scr, acc_scr)


def _scratch(G, block_q, D):
    """Online-softmax carry: running max and sum as (rows, 1) columns,
    the fp32 accumulator as (rows, D), one slab per query head."""
    return [pltpu.VMEM((G, block_q, 1), jnp.float32),
            pltpu.VMEM((G, block_q, 1), jnp.float32),
            pltpu.VMEM((G, block_q, D), jnp.float32)]


def _flash_causal_packed(qg, kg, vg, *, window, block, S, G, D, scale,
                         interpret):
    BK = qg.shape[0]
    n = S // block
    rows = (n + 1) // 2
    grid = (BK, rows, n + 1)

    def q_index(b, r, c):
        i, _, _, _, _ = _packed_coords(r, c, n)
        return (b, 0, i, 0)

    def kv_index(b, r, c):
        _, j, _, _, _ = _packed_coords(r, c, n)
        return (b, j, 0)

    return pl.pallas_call(
        functools.partial(
            _flash_kernel_packed, window=window, block=block, n=n,
            scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, G, block, D), q_index),
            pl.BlockSpec((1, block, D), kv_index),
            pl.BlockSpec((1, block, D), kv_index),
        ],
        out_specs=pl.BlockSpec((1, G, block, D), q_index),
        out_shape=jax.ShapeDtypeStruct((BK, G, S, D), qg.dtype),
        scratch_shapes=_scratch(G, block, D),
        interpret=interpret,
    )(qg, kg, vg)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_kv", "interpret",
                     "prune"))
def flash_attention(
    q: jax.Array,              # (B, S, H, D)
    k: jax.Array,              # (B, S, K, D)
    v: jax.Array,              # (B, S, K, D)
    *,
    causal: bool = True,
    window: int = 0,
    block_q: int = 512,
    block_kv: int = 1024,
    interpret: bool = False,
    prune: bool = True,
) -> jax.Array:
    B, S, H, D = q.shape
    K = k.shape[2]
    G = H // K
    block_q = min(block_q, S)
    block_kv = min(block_kv, S)
    assert S % block_q == 0 and S % block_kv == 0, (S, block_q, block_kv)
    scale = D ** -0.5

    # layout: fold kv heads into the grid; q as (B*K, G, S, D) so every
    # block's trailing dims are (rows, D)
    qg = q.reshape(B, S, K, G, D).transpose(0, 2, 3, 1, 4) \
        .reshape(B * K, G, S, D)
    kg = k.transpose(0, 2, 1, 3).reshape(B * K, S, D)
    vg = v.transpose(0, 2, 1, 3).reshape(B * K, S, D)

    if causal and prune and block_q == block_kv:
        out = _flash_causal_packed(
            qg, kg, vg, window=window, block=block_q, S=S, G=G, D=D,
            scale=scale, interpret=interpret)
    else:
        grid = (B * K, S // block_q, S // block_kv)
        out = pl.pallas_call(
            functools.partial(
                _flash_kernel, causal=causal, window=window,
                block_q=block_q, block_kv=block_kv, scale=scale),
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, G, block_q, D), lambda b, i, j: (b, 0, i, 0)),
                pl.BlockSpec((1, block_kv, D), lambda b, i, j: (b, j, 0)),
                pl.BlockSpec((1, block_kv, D), lambda b, i, j: (b, j, 0)),
            ],
            out_specs=pl.BlockSpec((1, G, block_q, D),
                                   lambda b, i, j: (b, 0, i, 0)),
            out_shape=jax.ShapeDtypeStruct((B * K, G, S, D), q.dtype),
            scratch_shapes=_scratch(G, block_q, D),
            interpret=interpret,
        )(qg, kg, vg)
    return out.reshape(B, K, G, S, D).transpose(0, 3, 1, 2, 4) \
        .reshape(B, S, H, D)
