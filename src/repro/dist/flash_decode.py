"""shard_map flash-decode over a sequence-sharded KV cache.

The data-organization pass spills the cache's *seq* dim onto the model
axis when kv_heads are not shardable (GQA kv=8 on a 16-wide TP axis).
Decode then needs two things XLA's automatic partitioner does badly on a
seq-sharded cache:

1. the one-token append — a dynamic-update-slice at a runtime offset on
   a sharded dim lowers to a gather; here only the *owning* shard writes,
   locally;
2. the attention reduction — each shard computes a partial online
   softmax ``(m, l, acc)`` over its seq slice and the three terms are
   combined across the model axis (one pmax + two psums of tiny
   per-query tensors instead of gathering the cache).

Semantics match :func:`repro.kernels.ref.decode_attention_ref` with
``cache_len = pos + 1``; ``pos`` is a *per-slot* ``(B,)`` vector (a
scalar is broadcast) so a continuous batch of mixed prompt lengths
appends and masks each slot at its own offset; ``window`` may be a
traced scalar.

:func:`flash_decode_paged` is the paged-residency twin: the cache is a
block pool + per-slot block table, the *pool* dim takes the mesh axes
(there is no contiguous seq dim to shard), and the same 3-term combine
runs over each shard's owned blocks.  On a data×model mesh the pool is
sharded over BOTH axes (2-D pool sharding): the block dim splits
data-major into one sub-pool per data shard, batch slots are
*partitioned* — not replicated — across data, each (data, model) shard
appends and attends only the blocks it owns, and the combine psums
across the model axis alone (a data shard's slots never need another
data shard's blocks, so no data-axis collective exists in the step).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.dist.sharding import mesh_sizes

NEG_INF = -1e30


def uses_seq_sharding(mesh, seq_len: int, model_axis: str = "model") -> bool:
    """Whether :func:`flash_decode` will actually run the seq-sharded
    shard_map path (vs its in-process single-shard combine).  The single
    source of truth for that dispatch — consumers reporting the decode
    path (``ServeEngine.decode_path``) must agree with it."""
    msize = mesh_sizes(mesh).get(model_axis, 1)
    return msize > 1 and seq_len % msize == 0


def combine_topology(mesh, *, model_axis: str = "model",
                     override=None) -> str:
    """Which model-axis softmax-combine topology a decode step runs —
    the single dispatch predicate shared by :func:`flash_decode`,
    :func:`flash_decode_paged` and ``ServeEngine.decode_path``
    (mirroring :func:`uses_seq_sharding` / :func:`pool_sharding_kind`).

    ``override`` is a plan- or caller-pinned topology ("flat" | "ring" |
    "bidir"); without one the cost model's calibrated thresholds choose.
    A degenerate model axis (degree <= 1) has no cross-shard combine, so
    it reports "flat" regardless of the override.
    """
    from repro.core.costmodel import (COMBINE_TOPOLOGIES,
                                      choose_combine_topology)
    msize = mesh_sizes(mesh).get(model_axis, 1)
    if msize <= 1:
        return "flat"
    if override is not None:
        if override not in COMBINE_TOPOLOGIES:
            raise ValueError(f"unknown combine topology {override!r}; "
                             f"expected one of {COMBINE_TOPOLOGIES}")
        return override
    return choose_combine_topology(msize)


def _ring_allgather(v: jax.Array, axis: str, n: int,
                    bidir: bool = False) -> jax.Array:
    """All-gather ``v`` into an ``(n, ...)`` source-indexed buffer via
    neighbor ppermutes: ``out[j]`` holds shard ``j``'s value on every
    shard.  ``bidir`` splits the walk across both ring directions —
    ``ceil((n-1)/2)`` forward + ``floor((n-1)/2)`` backward hops instead
    of ``n-1`` (the arms fill disjoint source slots: a collision would
    need ``t_fwd + t_bwd == n``, and the arms sum to at most ``n-1``).
    """
    idx = jax.lax.axis_index(axis)
    out = jnp.zeros((n,) + v.shape, v.dtype).at[idx].set(v)
    fwd = [(i, (i + 1) % n) for i in range(n)]
    bwd = [(i, (i - 1) % n) for i in range(n)]
    w = v
    for t in range(1, (n // 2 if bidir else n - 1) + 1):
        w = jax.lax.ppermute(w, axis, fwd)
        out = out.at[(idx - t) % n].set(w)
    if bidir:
        w = v
        for t in range(1, (n - 1) // 2 + 1):
            w = jax.lax.ppermute(w, axis, bwd)
            out = out.at[(idx + t) % n].set(w)
    return out


def _combine(m: jax.Array, l: jax.Array, acc: jax.Array,
             model_axis: str, msize: int, topology: str
             ) -> Tuple[jax.Array, jax.Array]:
    """Cross-shard online-softmax combine of partial ``(m, l, acc)``.

    * ``flat``  — pmax + two psums (three launches XLA fuses at small
      model degrees);
    * ``ring`` / ``bidir`` — ONE packed all-gather of the concatenated
      ``(m, l, acc)`` payload around the ring, then a local reduction.

    The local reduction folds sources *sequentially in source order* —
    the same order a host all-reduce applies — so ring and bidir are
    bitwise-identical to each other (same gathered buffer, same fold)
    and match flat to the last ulp (XLA fuses flat's reduce computation
    with the surrounding exp/mul, which can re-round one step; the
    multidevice oracle matrix pins both properties).
    """
    if topology == "flat":
        m_glob = jax.lax.pmax(m, model_axis)
        coef = jnp.exp(m - m_glob)
        return (jax.lax.psum(l * coef, model_axis),
                jax.lax.psum(acc * coef[..., None], model_axis))
    packed = jnp.concatenate([m[..., None], l[..., None], acc], axis=-1)
    g = _ring_allgather(packed, model_axis, msize,
                        bidir=(topology == "bidir"))
    ms, ls, accs = g[..., 0], g[..., 1], g[..., 2:]
    m_glob = ms[0]
    for i in range(1, msize):
        m_glob = jnp.maximum(m_glob, ms[i])
    coef = jnp.exp(ms - m_glob[None])
    lw, aw = ls * coef, accs * coef[..., None]
    l_glob, acc_glob = lw[0], aw[0]
    for i in range(1, msize):
        l_glob = l_glob + lw[i]
        acc_glob = acc_glob + aw[i]
    return l_glob, acc_glob


def _append(cache: jax.Array, new: jax.Array, idx: jax.Array,
            in_range: jax.Array) -> jax.Array:
    """Per-slot write of ``new[b]`` at seq offset ``idx[b]`` iff
    ``in_range[b]`` (else that slot is a no-op)."""
    def one(c, n, i, ok):
        upd = jax.lax.dynamic_update_slice_in_dim(
            c, n.astype(c.dtype), i, axis=0)
        return jnp.where(ok, upd, c)
    return jax.vmap(one)(cache, new, idx, in_range)


def _partial_attend(q: jax.Array, kc: jax.Array, vc: jax.Array,
                    kpos: jax.Array, pos: jax.Array, window: jax.Array,
                    extra_mask: jax.Array = None,
                    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Online-softmax partial terms (m, l, acc) over one seq slice.

    ``kpos`` holds the slice's *global* positions — ``(Sl,)`` shared, or
    ``(B, Sl)`` per slot (the paged path's compacted views differ per
    slot) — and ``pos`` the per-slot ``(B,)`` decode offsets, so the
    causal/window mask is exact per slot on every shard; fully-masked
    shards contribute weight ``exp(NEG_INF - m_global) == 0`` in the
    combine.  ``extra_mask`` (``(B, Sl)`` bool) additionally invalidates
    rows — the paged path's not-owned/unassigned blocks.
    """
    B, _, H, D = q.shape
    K = kc.shape[2]
    G = H // K
    qh = q[:, 0].reshape(B, K, G, D).astype(jnp.float32) * (D ** -0.5)
    s = jnp.einsum("bkgd,bskd->bkgs", qh, kc.astype(jnp.float32))
    kpos = kpos if kpos.ndim == 2 else kpos[None, :]            # (B|1, Sl)
    valid = kpos <= pos[:, None]                                # (B, Sl)
    valid &= jnp.where(window > 0, (pos[:, None] - kpos) < window, True)
    if extra_mask is not None:
        valid &= extra_mask
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bkgs,bskd->bkgd", p, vc.astype(jnp.float32))
    return m, l, acc


def _finish(q: jax.Array, l: jax.Array, acc: jax.Array) -> jax.Array:
    B, _, H, D = q.shape
    ctx = acc / jnp.maximum(l, 1e-30)[..., None]
    return ctx.reshape(B, H, D)[:, None].astype(q.dtype)


def flash_decode(q: jax.Array,            # (B, 1, H, D)
                 k_new: jax.Array,        # (B, 1, K, D)
                 v_new: jax.Array,        # (B, 1, K, D)
                 k_cache: jax.Array,      # (B, S, K, D)
                 v_cache: jax.Array,      # (B, S, K, D)
                 pos,                     # (B,) int per-slot offsets (scalar broadcast)
                 window=0,                # scalar int: 0 = full attention
                 *,
                 mesh: jax.sharding.Mesh,
                 data_axes: Tuple[str, ...] = ("data",),
                 model_axis: str = "model",
                 combine=None,
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One decode step against a (batch, seq)-sharded cache.

    Returns ``(ctx, k_cache', v_cache')`` with ``ctx`` of shape
    ``(B, 1, H, D)``.  Falls back to an unsharded single-shard path when
    the model axis cannot shard the seq dim (size 1 or non-divisible).
    ``combine`` pins the cross-shard softmax-combine topology (a plan's
    recorded ``comm.combine_topology``); ``None`` asks the shared
    :func:`combine_topology` predicate.
    """
    pos = jnp.asarray(pos, jnp.int32)
    window = jnp.asarray(window, jnp.int32)
    sizes = mesh_sizes(mesh)
    B, S = k_cache.shape[0], k_cache.shape[1]
    if pos.ndim == 0:
        pos = jnp.full((B,), pos, jnp.int32)

    if not uses_seq_sharding(mesh, S, model_axis):
        always = jnp.ones((B,), bool)
        kc = _append(k_cache, k_new, pos, always)
        vc = _append(v_cache, v_new, pos, always)
        m, l, acc = _partial_attend(q, kc, vc, jnp.arange(S), pos, window)
        return _finish(q, l, acc), kc, vc

    dnames = tuple(a for a in data_axes if a in sizes)
    import math
    dsize = math.prod(sizes[a] for a in dnames)
    bspec = None
    if dsize > 1 and B % dsize == 0:
        bspec = dnames[0] if len(dnames) == 1 else dnames
    msize = sizes[model_axis]
    topology = combine_topology(mesh, model_axis=model_axis,
                                override=combine)

    def local_fn(q, kn, vn, kc, vc, pos, window):
        Sl = kc.shape[1]
        start = jax.lax.axis_index(model_axis).astype(jnp.int32) * Sl
        lp = pos - start                  # (B,) per-slot local offsets
        in_range = (lp >= 0) & (lp < Sl)  # only the owning shard writes
        kc = _append(kc, kn, jnp.clip(lp, 0, Sl - 1), in_range)
        vc = _append(vc, vn, jnp.clip(lp, 0, Sl - 1), in_range)
        kpos = start + jnp.arange(Sl)
        m, l, acc = _partial_attend(q, kc, vc, kpos, pos, window)
        l_glob, acc_glob = _combine(m, l, acc, model_axis, msize, topology)
        return _finish(q, l_glob, acc_glob), kc, vc

    rep = P(bspec, None, None, None)
    shd = P(bspec, model_axis, None, None)
    # check_vma=False: the combine provably replicates ctx across the
    # model axis (psum/pmax), no need for the static replication checker
    fn = jax.shard_map(local_fn, mesh=mesh,
                       in_specs=(rep, rep, rep, shd, shd, P(bspec), P()),
                       out_specs=(rep, shd, shd), check_vma=False)
    return fn(q, k_new, v_new, k_cache, v_cache, pos, window)


# =====================================================================
# paged residency: the pool dim takes the model axis
# =====================================================================

def uses_pool_sharding(mesh, n_blocks: int, model_axis: str = "model") -> bool:
    """Whether :func:`flash_decode_paged` can run a pool-sharded
    shard_map path on the model axis alone (the 1-D predicate; see
    :func:`pool_sharding_kind` for the full data×model dispatch)."""
    msize = mesh_sizes(mesh).get(model_axis, 1)
    return msize > 1 and n_blocks % msize == 0


def pool_sharding_kind(mesh, n_blocks: int, batch: int,
                       data_axes: Tuple[str, ...] = ("data",),
                       model_axis: str = "model") -> str:
    """Which pool-sharded path :func:`flash_decode_paged` runs — the
    single dispatch predicate ``ServeEngine.decode_path`` (and its
    sub-pool block allocator) shares, mirroring
    :func:`uses_seq_sharding` for dense caches.

    ``"2d"``  — block dim sharded data-major over (data..., model) and
    the batch partitioned across data: needs a >1 data degree that
    divides both the batch (slots must be ownable per data shard) and,
    jointly with the model degree, the pool.
    ``"1d"``  — model-axis pool sharding only (the pool replicates over
    any data axes and the batch stays replicated with it).
    ``"none"`` — the in-process single-shard combine.
    """
    import math
    sizes = mesh_sizes(mesh)
    msize = sizes.get(model_axis, 1)
    dnames = tuple(a for a in data_axes if a in sizes)
    dsize = math.prod(sizes[a] for a in dnames) if dnames else 1
    if dsize > 1 and batch % dsize == 0 \
            and n_blocks and n_blocks % (dsize * msize) == 0:
        return "2d"
    if msize > 1 and n_blocks % msize == 0:
        return "1d"
    return "none"


def _partial_attend_paged(q, kp, vp, tbl, pos, window, start=0):
    """Partial (m, l, acc) over the blocks this shard owns.

    A slot can own at most ``min(nb, Nl)`` blocks on this shard, so the
    table is first *compacted* (owned entries sorted to the front) and
    only that many blocks are gathered and attended — per-shard reads
    and FLOPs stay ``~1/msize`` of the cache like the dense seq-sharded
    path, instead of every shard scoring the full masked view.
    Not-owned/unassigned rows are masked and contribute
    ``exp(NEG_INF - m_glob) == 0`` in the combine.
    """
    Nl, bl = kp.shape[0], kp.shape[1]
    B, nb = tbl.shape
    loc = tbl - start
    owned = (tbl >= 0) & (loc >= 0) & (loc < Nl)                # (B, nb)
    cols = min(nb, Nl)
    if cols < nb:
        # owned-first stable permutation of each slot's table columns;
        # the surviving column index still encodes the block's dense-
        # view position, so kpos rides along per slot
        order = jnp.argsort(jnp.where(owned, 0, 1), axis=1,
                            stable=True)[:, :cols]              # (B, cols)
        loc = jnp.take_along_axis(loc, order, axis=1)
        owned = jnp.take_along_axis(owned, order, axis=1)
        blk_pos = order                                         # (B, cols)
    else:
        blk_pos = jnp.broadcast_to(jnp.arange(nb), (B, nb))
    safe = jnp.clip(loc, 0, Nl - 1)
    kd = kp[safe].reshape(B, cols * bl, *kp.shape[2:])
    vd = vp[safe].reshape(B, cols * bl, *vp.shape[2:])
    kpos = (blk_pos[:, :, None] * bl
            + jnp.arange(bl)[None, None, :]).reshape(B, cols * bl)
    extra = jnp.repeat(owned, bl, axis=1)
    return _partial_attend(q, kd, vd, kpos, pos, window, extra_mask=extra)


def flash_decode_paged(q: jax.Array,       # (B, 1, H, D)
                       k_new: jax.Array,   # (B, 1, K, D)
                       v_new: jax.Array,   # (B, 1, K, D)
                       k_pool: jax.Array,  # (N, bl, K, D) block pool
                       v_pool: jax.Array,  # (N, bl, K, D)
                       block_tbl: jax.Array,  # (B, nb) ids; -1 unassigned
                       pos,                # (B,) per-slot offsets (scalar ok)
                       window=0,
                       *,
                       mesh: jax.sharding.Mesh,
                       data_axes: Tuple[str, ...] = ("data",),
                       model_axis: str = "model",
                       combine=None,
                       ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One decode step against a block-pool cache sharded on the *pool*
    dim (a paged cache has no contiguous seq dim to shard — the pool is
    the unit of placement, so each shard owns its slice of blocks and
    only the owner writes or attends over a block).

    Returns ``(ctx, k_pool', v_pool')`` with ``ctx`` ``(B, 1, H, D)``.
    Dispatch is :func:`pool_sharding_kind`:

    * ``"2d"`` — the block dim shards data-major over ``(data...,
      model)`` and the batch partitions across data.  Contract: every
      slot's table entries must point into the sub-pool of the data
      shard hosting that slot (``ServeEngine``'s allocator guarantees
      it) — a foreign-sub-pool block is owned by no shard in the slot's
      data row and is masked out of the combine.  Appends land on the
      one (data, model) shard owning the block; the softmax combine
      psums across model only.
    * ``"1d"`` — model-axis sharding only.  The pool *replicates* over
      any data axes (no batch dim to shard), so the batch stays
      replicated with it — batch-sharded appends would make each data
      replica append only its own slots' rows and silently diverge.
    * ``"none"`` — the unsharded single-shard combine.

    ``combine`` pins the model-axis softmax-combine topology (see
    :func:`combine_topology`); it changes the wire pattern of the
    combine, never its value.

    Semantics match :func:`repro.kernels.ref.paged_decode_attention_ref`
    over the appended pool with ``cache_len = pos + 1``.
    """
    pos = jnp.asarray(pos, jnp.int32)
    window = jnp.asarray(window, jnp.int32)
    B, N = block_tbl.shape[0], k_pool.shape[0]
    if pos.ndim == 0:
        pos = jnp.full((B,), pos, jnp.int32)

    from repro.models.lm import append_kv_paged

    kind = pool_sharding_kind(mesh, N, B, data_axes, model_axis)
    if kind == "none":
        kp = append_kv_paged(k_pool, k_new, pos, block_tbl)
        vp = append_kv_paged(v_pool, v_new, pos, block_tbl)
        m, l, acc = _partial_attend_paged(q, kp, vp, block_tbl, pos, window)
        return _finish(q, l, acc), kp, vp

    sizes = mesh_sizes(mesh)
    msize = sizes.get(model_axis, 1)
    topology = combine_topology(mesh, model_axis=model_axis,
                                override=combine)
    dnames = tuple(a for a in data_axes if a in sizes)
    if kind == "2d":
        bspec = dnames[0] if len(dnames) == 1 else dnames
        pool_assign = dnames + ((model_axis,) if model_axis in sizes else ())
    else:
        bspec = None
        pool_assign = (model_axis,)

    def local_fn(q, kn, vn, kp, vp, tbl, pos, window):
        Nl = kp.shape[0]
        # this shard's first global block id: data-major linearization of
        # its (data..., model) coordinates, matching the pool dim's
        # data-major PartitionSpec layout
        shard = jnp.zeros((), jnp.int32)
        if kind == "2d":
            for a in dnames:
                shard = shard * sizes[a] + jax.lax.axis_index(a)
        if model_axis in sizes:
            shard = shard * msize + jax.lax.axis_index(model_axis)
        start = shard.astype(jnp.int32) * Nl
        kp = append_kv_paged(kp, kn, pos, tbl, start)
        vp = append_kv_paged(vp, vn, pos, tbl, start)
        m, l, acc = _partial_attend_paged(q, kp, vp, tbl, pos, window, start)
        if msize > 1:
            l, acc = _combine(m, l, acc, model_axis, msize, topology)
        return _finish(q, l, acc), kp, vp

    rep = P(bspec, None, None, None)
    shd = P(pool_assign if len(pool_assign) > 1 else pool_assign[0],
            None, None, None)
    fn = jax.shard_map(local_fn, mesh=mesh,
                       in_specs=(rep, rep, rep, shd, shd,
                                 P(bspec, None), P(bspec), P()),
                       out_specs=(rep, shd, shd), check_vma=False)
    return fn(q, k_new, v_new, k_pool, v_pool, block_tbl, pos, window)
