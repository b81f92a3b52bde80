"""Paged KV residency: block-pool kernels vs oracles, the plan decision,
and the `repro plan` CLI.

The paged contract: attention/append over (pool, block table) must equal
the dense computation over the gathered view (`ref.paged_gather_ref`),
for every implementation — the XLA gather path, the scalar-prefetch
Pallas kernel, and the flash-decode paged combine (single-shard here;
the pool-sharded shard_map run lives in test_multidevice).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import paged_decode_attention
from repro.models import lm
from repro.models.attention import attention_decode_paged


def _pool_case(key, B=3, H=4, K=2, D=16, bl=8, N=10, nb=4, dtype=jnp.bfloat16):
    ks = jax.random.split(key, 5)
    q = jax.random.normal(ks[0], (B, H, D)).astype(dtype)
    kp = jax.random.normal(ks[1], (N, bl, K, D)).astype(dtype)
    vp = jax.random.normal(ks[2], (N, bl, K, D)).astype(dtype)
    kn = jax.random.normal(ks[3], (B, 1, K, D)).astype(dtype)
    vn = jax.random.normal(ks[4], (B, 1, K, D)).astype(dtype)
    # staggered tables: unassigned tails, non-contiguous blocks
    tbl = jnp.asarray([[0, 3, 7, -1], [5, 1, -1, -1], [2, 4, 6, 8]][:B],
                      jnp.int32)
    cl = jnp.asarray([17, 9, 32][:B], jnp.int32)
    return q, kp, vp, kn, vn, tbl, cl


def test_paged_gather_ref_dense_equivalence():
    """The gather oracle really is the dense view: scattering a dense
    cache into blocks and gathering back is the identity (valid rows)."""
    key = jax.random.PRNGKey(0)
    B, S, K, D, bl = 2, 32, 2, 8, 8
    dense = jax.random.normal(key, (B, S, K, D))
    nb = S // bl
    # slot 0 takes blocks 0..3, slot 1 blocks 4..7
    tbl = jnp.arange(B * nb, dtype=jnp.int32).reshape(B, nb)
    pool = dense.reshape(B * nb, bl, K, D)
    got = ref.paged_gather_ref(pool, tbl)
    assert np.allclose(np.asarray(got), np.asarray(dense))
    # unassigned entries gather as zeros
    got0 = ref.paged_gather_ref(pool, jnp.full((B, nb), -1, jnp.int32))
    assert float(jnp.abs(got0).max()) == 0.0


def test_append_kv_paged_matches_ref():
    q, kp, vp, kn, vn, tbl, cl = _pool_case(jax.random.PRNGKey(1))
    pos = jnp.asarray([16, 8, 31], jnp.int32)
    got = lm.append_kv_paged(kp, kn, pos, tbl)
    want = ref.paged_append_ref(kp, kn, pos, tbl)
    assert np.array_equal(np.asarray(got, np.float32),
                          np.asarray(want, np.float32))
    # a freed slot (all-unassigned row) must not write to the pool
    tbl2 = tbl.at[1].set(-1)
    got2 = lm.append_kv_paged(kp, kn, jnp.asarray([16, 0, 31]), tbl2)
    want2 = ref.paged_append_ref(kp, kn, jnp.asarray([16, 0, 31]), tbl2)
    assert np.array_equal(np.asarray(got2, np.float32),
                          np.asarray(want2, np.float32))
    assert np.array_equal(np.asarray(got2[tbl[1, 0]]), np.asarray(kp[tbl[1, 0]]))


def _stacked_case(key, window, L=3, H=4, K=2, D=16, bl=8, N=12,
                  dtype=jnp.bfloat16):
    """Stacked ``(L, N, bl, K, D)`` pools and slots of mixed lengths: a
    full ``nb x bl`` slot, a free slot (table row -1, decoded at length
    1 as the engine does), a length-1 slot, and two partial ones.  The
    poisoned twin holds NaN in every pool row no slot's query sees (past
    its length, before its window, or in no slot's block)."""
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (5, H, D)).astype(dtype)
    kp = jax.random.normal(ks[1], (L, N, bl, K, D)).astype(dtype)
    vp = jax.random.normal(ks[2], (L, N, bl, K, D)).astype(dtype)
    tbl = np.asarray([[4, 9, 2, 7], [-1, -1, -1, -1], [11, -1, -1, -1],
                      [0, 5, 3, -1], [8, 10, -1, -1]], np.int32)
    cl = np.asarray([4 * bl, 1, 1, 2 * bl + 5, bl + 1], np.int32)
    seen = np.zeros((N, bl), bool)
    for b in range(len(cl)):
        lo = cl[b] - window if window > 0 else 0
        for pos in range(max(lo, 0), cl[b]):
            if tbl[b, pos // bl] >= 0:
                seen[tbl[b, pos // bl], pos % bl] = True
    hide = jnp.asarray(~seen)[None, :, :, None, None]
    poison = lambda p: jnp.where(hide, jnp.nan, p).astype(dtype)
    return (q, kp, vp, poison(kp), poison(vp), jnp.asarray(tbl),
            jnp.asarray(cl))


@pytest.mark.parametrize("layout", ["pool", "stacked"])
@pytest.mark.parametrize("window", [0, 6])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_paged_decode_attention_kernel_vs_oracle(layout, window, dtype):
    """The scalar-prefetch Pallas kernel (interpret mode) streams blocks
    via the table and matches the gather oracle.  On stacked pools it
    reads the layer it is given, per layer, and only each slot's live
    blocks: with every unseen pool row NaN the output stays finite."""
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    if layout == "pool":
        q, kp, vp, *_, tbl, cl = _pool_case(jax.random.PRNGKey(2),
                                            dtype=dtype)
        got = paged_decode_attention(q, kp[None], vp[None], tbl,
                                     cache_len=cl, window=window,
                                     interpret=True)
        want = ref.paged_decode_attention_ref(q, kp, vp, tbl, cache_len=cl,
                                              window=window)
        err = np.abs(np.asarray(got, np.float32)
                     - np.asarray(want, np.float32)).max()
        assert err < tol, (window, err)
        return
    q, kp, vp, kbad, vbad, tbl, cl = _stacked_case(
        jax.random.PRNGKey(12), window, dtype=dtype)
    for layer in range(kp.shape[0]):
        got = paged_decode_attention(q, kbad, vbad, tbl, cache_len=cl,
                                     window=window, layer=layer,
                                     interpret=True)
        want = ref.paged_decode_attention_ref(q, kp[layer], vp[layer], tbl,
                                              cache_len=cl, window=window)
        got = np.asarray(got, np.float32)
        assert np.isfinite(got).all(), (layer, window)
        err = np.abs(got - np.asarray(want, np.float32)).max()
        assert err < tol, (layer, window, err)


@pytest.mark.parametrize("window", [0, 6])
def test_paged_decode_fetches_only_live_blocks(window):
    """The kernel's grid fetches a block only where an entry is live
    (assigned, and holding a row the query sees): every other step names
    the block its predecessor fetched, which Pallas does not copy again.
    So the number of block changes along the grid is the live count."""
    from repro.kernels.decode_attention import _fetch_plan
    *_, tbl, cl = _stacked_case(jax.random.PRNGKey(12), window)
    bl = 8
    fetch, live = _fetch_plan(tbl, cl, jnp.int32(window), bl)
    fetch, live = np.asarray(fetch).reshape(-1), np.asarray(live)
    want = np.zeros_like(live)
    for b, n in enumerate(np.asarray(cl)):
        lo = max(n - window, 0) if window else 0
        for j in range(tbl.shape[1]):
            want[b, j] = (tbl[b, j] >= 0 and j * bl < n
                          and (j + 1) * bl > lo)
    assert np.array_equal(live, want)
    flat_live = live.reshape(-1).astype(bool)
    assert np.array_equal(fetch[flat_live],
                          np.asarray(tbl).reshape(-1)[flat_live])
    changes = 1 + np.count_nonzero(fetch[1:] != fetch[:-1])
    assert changes == flat_live.sum()


def test_paged_decode_attention_xla_gather_vs_oracle():
    q, kp, vp, *_, tbl, cl = _pool_case(jax.random.PRNGKey(3))
    got = attention_decode_paged(q[:, None], kp, vp, tbl, cache_len=cl)
    want = ref.paged_decode_attention_ref(q, kp, vp, tbl, cache_len=cl)
    err = np.abs(np.asarray(got[:, 0], np.float32)
                 - np.asarray(want, np.float32)).max()
    assert err < 2e-2, err


@pytest.mark.parametrize("window", [0, 6])
def test_flash_decode_paged_single_shard_vs_oracle(window):
    from repro.launch.mesh import make_host_mesh
    from repro.dist.flash_decode import flash_decode_paged
    mesh = make_host_mesh()
    q, kp, vp, kn, vn, tbl, cl = _pool_case(jax.random.PRNGKey(4),
                                            dtype=jnp.float32)
    pos = jnp.asarray([16, 8, 30], jnp.int32)
    ctx, kp2, vp2 = jax.jit(
        lambda *a: flash_decode_paged(*a, mesh=mesh))(
            q[:, None], kn, vn, kp, vp, tbl, pos, window)
    kr = ref.paged_append_ref(kp, kn, pos, tbl)
    vr = ref.paged_append_ref(vp, vn, pos, tbl)
    r = ref.paged_decode_attention_ref(q, kr, vr, tbl, cache_len=pos + 1,
                                       window=window)
    assert float(jnp.abs(ctx[:, 0] - r).max()) < 1e-5
    assert np.allclose(np.asarray(kp2), np.asarray(kr))
    assert np.allclose(np.asarray(vp2), np.asarray(vr))


def test_decode_step_paged_matches_dense_cache():
    """One lm.decode_step over a paged cache == the same step over the
    equivalent dense cache (same staggered fill), logits and appended
    rows both."""
    from repro.configs import get_arch
    from repro.models.lm import RunCfg
    cfg = RunCfg(block_q=16, ssd_chunk=16)
    arch = get_arch("qwen3-8b").reduced()
    params = lm.init_params(arch, jax.random.PRNGKey(5))
    B, max_len, bl = 2, 32, 16
    plens = [5, 11]
    dense = lm.init_cache(arch, B, max_len)
    paged = lm.init_paged_cache(arch, B, max_len, bl, 2 * (max_len // bl))
    toks = []
    for slot, plen in enumerate(plens):
        p = (np.arange(plen, dtype=np.int32) * 3 + slot) % arch.vocab_size
        lg, c1 = lm.prefill(arch, params,
                            {"tokens": jnp.asarray(p[None], jnp.int32)},
                            cfg, max_len=max_len)
        for key in ("k", "v"):
            dense[key] = dense[key].at[:, slot].set(c1[key][:, 0])
        toks.append(int(jnp.argmax(lg[0, :arch.vocab_size])))
    # paged layout: slot 0 owns blocks [0, 1], slot 1 owns [2, 3]
    nb = max_len // bl
    tbl = np.asarray([[0, 1], [2, 3]], np.int32)
    for key in ("k", "v"):
        pool = dense[key].reshape(dense[key].shape[0], B * nb, bl,
                                  *dense[key].shape[3:])
        paged[key] = pool
    paged["block_tbl"] = jnp.asarray(tbl)
    pos = jnp.asarray(plens, jnp.int32)
    dense["pos"] = pos
    paged["pos"] = pos
    t = jnp.asarray(toks, jnp.int32)[:, None]
    lg_d, dense2 = lm.decode_step(arch, params, dense, {"tokens": t}, cfg)
    lg_p, paged2 = lm.decode_step(arch, params, paged, {"tokens": t}, cfg)
    err = np.abs(np.asarray(lg_d, np.float32)
                 - np.asarray(lg_p, np.float32)).max()
    assert err < 1e-3, err
    # the appended pool rows match the dense appended rows
    for key in ("k", "v"):
        dview = dense2[key].reshape(dense2[key].shape[0], B * nb, bl,
                                    *dense2[key].shape[3:])
        assert np.allclose(np.asarray(paged2[key], np.float32),
                           np.asarray(dview, np.float32))


# ---------------- cross-request block aliasing ----------------
#
# The sharing contract every paged reader must honour: a block id that
# appears in TWO slots' tables (a refcounted prefix hit) reads exactly
# like a private copy of the same rows.  Readers are pure functions of
# (pool, table) — any kernel that mutated its streamed blocks, or
# special-cased duplicate ids, would break aliased decoding.

def _aliased_vs_private_case(key, bl=8, K=2, D=16, dtype=jnp.float32):
    """Two slots share blocks {0, 3} as their 2-block prefix; slot 0
    appends into private block 7, slot 1 into private block 5.  The
    private twin duplicates the shared rows into blocks {2, 6} so slot 1
    no longer aliases slot 0."""
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (2, 4, D)).astype(dtype)
    kp = jax.random.normal(ks[1], (10, bl, K, D)).astype(dtype)
    vp = jax.random.normal(ks[2], (10, bl, K, D)).astype(dtype)
    tbl_alias = jnp.asarray([[0, 3, 7, -1], [0, 3, 5, -1]], jnp.int32)
    kp_priv = kp.at[2].set(kp[0]).at[6].set(kp[3])
    vp_priv = vp.at[2].set(vp[0]).at[6].set(vp[3])
    tbl_priv = jnp.asarray([[0, 3, 7, -1], [2, 6, 5, -1]], jnp.int32)
    cl = jnp.asarray([2 * bl + 3, 2 * bl + 6], jnp.int32)
    return q, kp, vp, kp_priv, vp_priv, tbl_alias, tbl_priv, cl


def test_aliased_tables_read_identical_xla_gather():
    q, kp, vp, kpp, vpp, ta, tp, cl = _aliased_vs_private_case(
        jax.random.PRNGKey(7))
    got = attention_decode_paged(q[:, None], kp, vp, ta, cache_len=cl)
    want = attention_decode_paged(q[:, None], kpp, vpp, tp, cache_len=cl)
    assert np.array_equal(np.asarray(got, np.float32),
                          np.asarray(want, np.float32))


@pytest.mark.parametrize("window", [0, 6])
def test_aliased_tables_read_identical_pallas(window):
    q, kp, vp, kpp, vpp, ta, tp, cl = _aliased_vs_private_case(
        jax.random.PRNGKey(8))
    got = paged_decode_attention(q, kp[None], vp[None], ta, cache_len=cl,
                                 window=window, interpret=True)
    want = paged_decode_attention(q, kpp[None], vpp[None], tp,
                                  cache_len=cl, window=window,
                                  interpret=True)
    assert np.array_equal(np.asarray(got, np.float32),
                          np.asarray(want, np.float32))


def test_aliased_tables_read_identical_flash_and_append_private():
    """flash-decode over aliased tables matches the private twin — and
    the fused append only writes each slot's PRIVATE tail block (the
    engine's CoW barrier guarantees no slot ever appends into a block
    with refcount > 1, so appends land on distinct ids here)."""
    from repro.launch.mesh import make_host_mesh
    from repro.dist.flash_decode import flash_decode_paged
    mesh = make_host_mesh()
    q, kp, vp, kpp, vpp, ta, tp, cl = _aliased_vs_private_case(
        jax.random.PRNGKey(9))
    kn = jax.random.normal(jax.random.PRNGKey(10), (2, 1, 2, 16))
    vn = jax.random.normal(jax.random.PRNGKey(11), (2, 1, 2, 16))
    pos = cl
    run = jax.jit(lambda kk, vv, tt: flash_decode_paged(
        q[:, None], kn, vn, kk, vv, tt, pos, 0, mesh=mesh))
    ctx_a, kp2, vp2 = run(kp, vp, ta)
    ctx_p, kpp2, vpp2 = run(kpp, vpp, tp)
    assert np.array_equal(np.asarray(ctx_a, np.float32),
                          np.asarray(ctx_p, np.float32))
    # appends landed in private blocks 7 and 5 under both layouts, and
    # the shared prefix blocks 0 and 3 were left untouched
    for b in (5, 7):
        assert np.array_equal(np.asarray(kp2[b]), np.asarray(kpp2[b]))
        assert np.array_equal(np.asarray(vp2[b]), np.asarray(vpp2[b]))
    for b in (0, 3):
        assert np.array_equal(np.asarray(kp2[b]), np.asarray(kp[b]))


# ---------------- the prefix cache ----------------

def test_chain_hashes_properties():
    from repro.serve.prefix_cache import chain_hashes
    t = np.arange(40, dtype=np.int32)
    h = chain_hashes(t, 16)
    assert len(h) == 2                       # partial tail never hashed
    assert chain_hashes(t[:32], 16) == h     # pure prefix function
    # chaining: a change in block 0 reflows every downstream hash
    t2 = t.copy()
    t2[0] += 1
    h2 = chain_hashes(t2, 16)
    assert h2[0] != h[0] and h2[1] != h[1]
    # a change confined to block 1 keeps block 0's hash
    t3 = t.copy()
    t3[20] += 1
    h3 = chain_hashes(t3, 16)
    assert h3[0] == h[0] and h3[1] != h[1]
    assert chain_hashes(t[:15], 16) == []
    assert chain_hashes(t, 0) == []
    # dtype-stable: the engine hashes int64 so int32/int64 feeds agree
    assert chain_hashes(t.astype(np.int64), 16) == h


def test_prefix_cache_match_insert_evict():
    from repro.serve.prefix_cache import PrefixCache, chain_hashes
    pc = PrefixCache(groups=2)
    t = np.arange(48, dtype=np.int32)
    h = chain_hashes(t, 16)                  # 3 chained block hashes
    pc.insert(h, [4, 9, 2], group=0)
    assert len(pc) == 3
    # longest-prefix walk, and divergence stops the descent
    assert pc.match(h, group=0) == [4, 9, 2]
    assert pc.match(h[:2], group=0) == [4, 9]
    div = chain_hashes(np.concatenate([t[:16], t[:32]]), 16)
    assert pc.match(div, group=0) == [4]     # block 0 equal, then split
    # sub-pool isolation: group 1's trie is empty
    assert pc.match(h, group=1) == []
    # first-writer-wins: a second resident with the same prefix does
    # not steal the mapping (its blocks are the refcount aliases)
    pc.insert(h, [7, 8, 1], group=0)
    assert pc.match(h, group=0) == [4, 9, 2]
    # evicting a middle block prunes that entry only; the walk now
    # stops at the gap (the trailing block is unreachable by prefix)
    pc.evict([9])
    assert pc.match(h, group=0) == [4]
    pc.evict([4, 2, 99])                     # unknown ids are ignored
    assert len(pc) == 0 and pc.match(h, group=0) == []
    st = pc.stats()
    assert st["trie_blocks"] == 0


# ---------------- the plan decision ----------------

def test_kv_residency_plan_decision():
    from repro.configs import ShapeConfig
    from repro.core.pipeline import specialize
    # model-only mesh (data degree 1): one sub-pool, model-shardable
    plan = specialize("qwen2-vl-72b", "decode_32k", mesh_shape=(1, 16))
    assert plan.estimates["kv_residency"] == "paged"
    assert plan.estimates["kv_block_len"] >= 16
    assert plan.estimates["kv_n_blocks"] >= 1
    assert plan.estimates["kv_n_blocks"] % 16 == 0      # model-shardable
    assert plan.estimates["kv_pool_data_degree"] == 1
    assert plan.estimates["kv_paged_bytes"] <= plan.estimates["kv_dense_bytes"]
    assert any(s == "kv_residency" for _, s, _, _ in plan.log)
    # prefix reuse rides on every paged plan, with its headroom estimate
    # and a decision-log entry carrying the hit-rate bet
    assert plan.estimates["kv_prefix_reuse"] == "on"
    assert plan.estimates["kv_prefix_hit_headroom"] >= 0
    assert any(s == "kv_prefix_reuse" and "aliased" in why
               for _, s, _, why in plan.log)

    # a >1 data degree now 2-D-shards the pool (data-major sub-pools,
    # batch partitioned across data) instead of forcing dense — and the
    # per-chip paged bytes land BELOW the dense stripes they replace
    dp = specialize("qwen2-vl-72b", "decode_32k")       # 16x16 mesh
    assert dp.estimates["kv_residency"] == "paged"
    assert dp.estimates["kv_pool_data_degree"] == 16
    assert dp.estimates["kv_pool_model_degree"] == 16
    assert dp.estimates["kv_n_blocks"] % (16 * 16) == 0  # 2-D-shardable
    assert dp.estimates["kv_paged_bytes"] < dp.estimates["kv_dense_bytes"]
    assert any(s == "kv_residency" and "2-D" in why
               for _, s, _, why in dp.log)

    # ...but a batch that cannot partition over the data degree would
    # force the pool back to data-replication: honestly dense
    odd = specialize("qwen3-8b",
                     ShapeConfig("decode_odd_batch", "decode", 512, 3),
                     mesh_shape=(2, 4))
    assert odd.estimates["kv_residency"] == "dense"
    assert any(s == "kv_residency" and "partition" in why
               for _, s, _, why in odd.log)

    # too shallow for >=2 blocks/seq -> dense
    shallow = specialize("qwen3-8b",
                         ShapeConfig("decode_shallow", "decode", 16, 2),
                         mesh_shape=(1, 1))
    assert shallow.estimates["kv_residency"] == "dense"
    assert "kv_block_len" not in shallow.estimates

    # option override forces either way (and is part of the request key)
    forced = specialize("qwen2-vl-72b", "decode_32k", mesh_shape=(1, 16),
                        kv_residency="dense")
    assert forced.estimates["kv_residency"] == "dense"

    # training shapes and SSM-only archs never page
    train = specialize("qwen3-8b", "train_4k")
    assert "kv_residency" not in train.estimates
    ssm = specialize("mamba2-2.7b", "long_500k")
    assert "kv_residency" not in ssm.estimates


def test_costmodel_kv_block_geometry():
    from repro.core.costmodel import kv_block_geometry
    tok = 2 * 80 * 8 * 128 * 2      # K and V, 80 layers of 8x128, bf16
    geo = kv_block_geometry(32768, 128, tok)
    assert geo.block_len == 512
    assert geo.blocks_per_seq == 64
    assert geo.n_blocks == 128 * 64           # uncapped: dense worst case
    assert geo.paged_bytes == geo.dense_bytes
    # a budget cap shrinks the pool but never below one full sequence
    capped = kv_block_geometry(32768, 128, tok,
                               budget_bytes=geo.dense_bytes / 4)
    assert geo.n_blocks / 4.1 < capped.n_blocks <= geo.n_blocks // 4
    tiny = kv_block_geometry(32768, 128, tok, budget_bytes=1.0)
    assert tiny.n_blocks == tiny.blocks_per_seq
    # zero headroom is a real cap (the one-sequence floor), NOT uncapped
    zero = kv_block_geometry(32768, 128, tok, budget_bytes=0.0)
    assert zero.n_blocks == zero.blocks_per_seq
    # 2-D: the data degree still divides capacity (the reclamation
    # bet), but the pool splits into data_shards sub-pools, each
    # model-aligned and never below one sequence — the 16x16 case's
    # raw 512-block target bumps to the 16 x 64-block sub-pool floor
    dp = kv_block_geometry(32768, 128, tok, data_shards=16, align=16)
    assert dp.data_degree == 16 and dp.model_degree == 16
    assert dp.n_blocks == 16 * 64           # 16 sub-pools at the floor
    assert dp.sub_pool_blocks == 64 and dp.n_blocks % (16 * 16) == 0
    assert dp.paged_bytes < dp.dense_bytes
    wide = kv_block_geometry(32768, 2048, tok,
                             data_shards=16, align=16)
    assert wide.n_blocks == 2048 * 64 // 16     # bet above the floor
    assert wide.sub_pool_blocks % 16 == 0
    small = 2 * 2 * 2 * 16 * 2
    odd = kv_block_geometry(64, 3, small, align=8)        # want=12 -> 8
    assert odd.n_blocks == 8
    floor = kv_block_geometry(64, 1, small, align=8)      # per_seq=4 -> 8
    assert floor.n_blocks == 8
    # prefix-reuse capacity math: r/(h + r(1-h)) approaches 1/(1-h),
    # headroom is (r-1)*floor(h*blocks_per_seq) capped at the sub-pool,
    # and both collapse to the no-op when reuse is off or r <= 1
    assert geo.prefix_capacity_factor(1) == 1.0
    f8 = geo.prefix_capacity_factor(8)
    assert 1.0 < f8 < geo.prefix_capacity_factor(64) < 2.0   # h = 0.5
    assert geo.prefix_hit_headroom(1) == 0
    per = geo.blocks_per_seq
    assert geo.prefix_hit_headroom(2) == int(0.5 * per)
    assert geo.prefix_hit_headroom(10 ** 6) <= geo.sub_pool_blocks
    assert geo.prefix_hit_headroom(4, hit_rate=1.0) == 3 * per
    import dataclasses as _dc
    off = _dc.replace(geo, prefix_reuse="off")
    assert off.prefix_capacity_factor(8) == 1.0
    assert off.prefix_hit_headroom(8) == 0


# ---------------- the `repro plan` CLI ----------------

def test_plan_cli_list_show_diff(capsys, tmp_path):
    from repro.core.pipeline import specialize
    from repro.launch.plan import main
    d = str(tmp_path / "plans")
    a = specialize("qwen3-8b", "train_4k", plan_dir=d)
    b = specialize("qwen3-8b", "train_4k", plan_dir=d, decode_impl="xla")

    assert main(["--plan-dir", d, "list"]) == 0
    out = capsys.readouterr().out
    assert a.content_hash()[:12] in out and "qwen3-8b" in out

    assert main(["--plan-dir", d, "show", a.content_hash()[:10],
                 "--log"]) == 0
    out = capsys.readouterr().out
    assert a.content_hash() in out
    assert "train seq=4096 batch=256" in out
    assert "[data_organization]" in out

    rc = main(["--plan-dir", d, "diff", a.content_hash()[:10],
               b.content_hash()[:10]])
    out = capsys.readouterr().out
    if a.content_hash() != b.content_hash():
        assert rc == 1
    else:
        assert rc == 0 and "identical" in out

    assert main(["--plan-dir", d, "diff", a.content_hash()[:10],
                 a.content_hash()[:10]]) == 0
    assert "identical" in capsys.readouterr().out

    with pytest.raises(SystemExit, match="no stored plan"):
        main(["--plan-dir", d, "show", "ffffffffffff"])


def test_plan_cli_renders_tier_decisions_schema_tolerant(capsys, tmp_path):
    """`plan show` renders the new tier fields, and artifacts stored
    before the multi-tier refactor (no ``kv_tier_split`` key) display
    as an hbm-only pool instead of raising or dropping the field."""
    from types import SimpleNamespace

    from repro.configs import ShapeConfig
    from repro.core.pipeline import specialize
    from repro.launch.plan import _decisions, main
    d = str(tmp_path / "plans")
    plan = specialize("qwen3-8b", ShapeConfig("tiered", "decode", 64, 2),
                      mesh_shape=(1, 1), plan_dir=d)
    assert main(["--plan-dir", d, "show", plan.content_hash()[:10]]) == 0
    out = capsys.readouterr().out
    assert '"kv_tier_split": "hbm+host"' in out
    assert '"kv_host_blocks"' in out and '"kv_prefetch": "on"' in out

    # a pre-tier paged artifact: same decisions minus every tier key
    est = {k: v for k, v in plan.estimates.items()
           if k not in ("kv_tier_split", "kv_host_blocks", "kv_prefetch")}
    dec = _decisions(SimpleNamespace(estimates=est))
    assert dec["kv_tier_split"] == "hbm-only"
    assert "kv_host_blocks" not in dec and "kv_prefetch" not in dec
    # dense plans get no synthesized tier field — there is no pool
    dense = _decisions(SimpleNamespace(estimates={"kv_residency": "dense"}))
    assert "kv_tier_split" not in dense


def test_plan_cli_verify_reports_corrupt_and_stale(capsys, tmp_path):
    from repro.core.pipeline import specialize
    from repro.launch.plan import main
    import json as _json

    from repro.configs import ShapeConfig
    d = tmp_path / "plans"
    plans = [specialize("qwen3-8b",
                        ShapeConfig(f"verify_{i}", "decode", seq, 2),
                        mesh_shape=(1, 1), plan_dir=str(d))
             for i, seq in enumerate((32, 64, 128))]
    files = [d / f"{p.content_hash()}.json" for p in plans]
    assert len({f.name for f in files}) == 3 and all(f.exists()
                                                     for f in files)

    # a healthy store verifies clean
    assert main(["--plan-dir", str(d), "verify"]) == 0
    out = capsys.readouterr().out
    assert "0 bad" in out and "0 dangling" in out

    # truncate one entry -> corrupt; stamp another stale; tamper the
    # third's payload (valid JSON, wrong hash — only the re-hash sees
    # it); dangle a by_key ref
    files[0].write_text(files[0].read_text()[:40])
    e = _json.loads(files[1].read_text())
    e["schema"] = -1
    files[1].write_text(_json.dumps(e))
    e = _json.loads(files[2].read_text())
    e["plan"]["arch"] = "tampered"
    files[2].write_text(_json.dumps(e))
    (d / "by_key").mkdir(exist_ok=True)
    (d / "by_key" / "deadbeef").write_text("f" * 64)
    assert main(["--plan-dir", str(d), "verify"]) == 1
    out = capsys.readouterr().out
    assert "corrupt" in out and "stale-schema" in out
    assert "3 bad" in out and "dangling" in out


def test_plan_cli_gc_manual_eviction(capsys, tmp_path):
    from repro.configs import ShapeConfig
    from repro.core.pipeline import specialize
    from repro.launch.plan import main
    d = tmp_path / "plans"
    for i, seq in enumerate((32, 64, 128)):
        specialize("qwen3-8b", ShapeConfig(f"gc_{i}", "decode", seq, 2),
                   mesh_shape=(1, 1), plan_dir=str(d))
    assert len(list(d.glob("*.json"))) == 3
    assert main(["--plan-dir", str(d), "gc", "--max-entries", "1"]) == 0
    out = capsys.readouterr().out
    assert "removed 2" in out
    assert len(list(d.glob("*.json"))) == 1
    # surviving store verifies clean (refs were trimmed with entries)
    assert main(["--plan-dir", str(d), "verify"]) == 0
