"""Property-based tests on the system's invariants.

Two tiers: hypothesis-driven properties (skipped when hypothesis is not
installed) and seeded stdlib-random fuzz that always runs — the block-
allocator suite is in the second tier so the serving layer's invariants
are exercised in every CI environment, not only where hypothesis
happens to be available.
"""

import math
import random

import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:          # pragma: no cover - depends on environment
    HAVE_HYPOTHESIS = False

from repro.core.costmodel import (MeshModel, allgather_bytes, allreduce_bytes,
                                  kv_block_geometry, reduce_scatter_bytes)
from repro.dist.collectives import dequantize_int8, ef_compress, quantize_int8
from repro.dist.sharding import resolve_pspec
from repro.launch.mesh import make_mesh
from repro.models.moe import _capacity
from repro.serve.allocator import BlockAllocator


AXIS_NAMES = [None, "batch", "embed", "heads", "ff", "vocab"]
RULES = {"batch": "data", "embed": None, "heads": "model", "ff": "model",
         "vocab": "model"}
SIZES = {"data": 16, "model": 16}


# =====================================================================
# block-allocator fuzz: randomized admit/finish/exhaustion/churn
# sequences against the paged serving layer's invariants, on both 1-D
# (one global pool) and 2-D (per-data-shard sub-pool) geometries.
# Runs on seeded stdlib random so it exercises in every environment;
# a hypothesis twin below widens the sequences when available.
# =====================================================================

#: (n_blocks, groups, host_blocks): 1-D pools and 2-D data-degree
#: sub-pool splits, with and without a host spill tier behind them
POOL_GEOMETRIES = [(8, 1, 0), (24, 1, 16), (16, 2, 8), (32, 4, 0),
                   (64, 8, 32)]


def _fuzz_allocator(n_blocks: int, groups: int, ops, max_need: int,
                    host_blocks: int = 0):
    """Drive one admit/grant/retain/spill/promote/finish sequence,
    asserting every invariant the serving engine relies on after each
    step.

    ``ops`` yields (kind, group, need, pick) tuples; kind < 0.35 admits
    a multi-block budget, kind < 0.5 is a one-block grow-on-demand
    grant appended to a random live holder, kind < 0.6 retains a random
    live holder's blocks into a new alias holder (a prefix-cache hit),
    kind < 0.7 spills a holder's *private* HBM blocks to the host tier
    (shared blocks stay put in the fuzz — the other holders' lists
    would go stale; the engine re-keys every table on a shared spill),
    kind < 0.8 promotes a holder's private host blocks into the op's
    sub-pool, kind < 0.85 starts a new low-water epoch, else a random
    live holder finishes — its blocks only come back to the free list
    once every alias has finished too.  Returns the live set for the
    caller's drain check.

    The ``refs`` model (block -> holder count) encodes *no grant after
    free* AND *no free while shared* directly: a block leaves the model
    only when its last holder releases it, so a grant handing out a
    block some holder still owns — freed out from under it, or freed
    while a sharer survived — trips the double-assignment assert.  The
    ``water`` model is exact: the watermark equals the minimum free
    count since the last epoch reset, pinned with equality — the
    ratchet-forever bug (a watermark that survives a reset) and a
    watermark that misses a spill/promote draw both trip it.
    """
    alloc = BlockAllocator(n_blocks, groups, host_blocks=host_blocks)
    sub = n_blocks // groups
    live = []                     # allocations currently held
    refs = {}                     # model: block id -> holder count
    water = [alloc.low_water(g) for g in range(groups)]
    epochs = 0
    for kind, group, need, pick in ops:
        if kind < 0.35 or not live:
            got = alloc.allocate(need, group)
            if got is None:
                # exhaustion is exact: refusal iff the sub-pool cannot
                # cover the request (head-of-line wait in the engine)
                assert need > alloc.free_in(group)
            else:
                assert len(got) == need
                assert not (set(got) & set(refs)), "double-assigned block"
                assert all(b // sub == group for b in got), \
                    "allocation crossed a sub-pool boundary"
                for b in got:
                    refs[b] = 1
                live.append(got)
        elif kind < 0.5:
            # grow-on-demand: one-block grant onto a live holder
            blk = alloc.allocate_one(group)
            if blk is None:
                assert alloc.free_in(group) == 0
            else:
                assert blk not in refs, "granted a freed/held block"
                assert blk // sub == group
                refs[blk] = 1
                live[pick % len(live)].append(blk)
        elif kind < 0.6:
            # prefix-cache hit: alias an existing holder's blocks
            got = list(live[pick % len(live)])
            alloc.retain(got)
            for b in got:
                refs[b] += 1
            live.append(got)
        elif kind < 0.7 and host_blocks:
            # spill: one holder's private HBM blocks move to host ids,
            # all-or-none (a partial spill would strand the holder)
            holder = live[pick % len(live)]
            cand = [b for b in holder if b < n_blocks and refs[b] == 1]
            pairs = alloc.spill(cand)
            if pairs is None:
                assert len(cand) > alloc.host_free, \
                    "spill refused despite host headroom"
            else:
                assert [o for o, _ in pairs] == cand
                for o, h in pairs:
                    assert h >= n_blocks, "spill produced an HBM id"
                    refs[h] = refs.pop(o)
                    holder[holder.index(o)] = h
        elif kind < 0.8 and host_blocks:
            # promote: one holder's private host blocks move back into
            # the op's sub-pool (group integrity by construction)
            holder = live[pick % len(live)]
            cand = [b for b in holder if b >= n_blocks and refs[b] == 1]
            pairs = alloc.promote(cand, group)
            if pairs is None:
                assert len(cand) > alloc.free_in(group), \
                    "promote refused despite sub-pool headroom"
            else:
                for h, b in pairs:
                    assert b // sub == group, "promote crossed a sub-pool"
                    refs[b] = refs.pop(h)
                    holder[holder.index(h)] = b
        elif kind < 0.85:
            # rebalance-cycle epoch boundary: the watermark snaps to
            # the current free count instead of ratcheting forever
            alloc.reset_low_water()
            epochs += 1
            assert alloc.low_water_epochs == epochs
            for g in range(groups):
                water[g] = alloc.free_in(g)
        else:
            got = live.pop(pick % len(live))
            freed = alloc.release(got)
            want_freed = set()
            for b in got:
                refs[b] -= 1
                if refs[b] == 0:
                    del refs[b]
                    want_freed.add(b)
            assert set(freed) == want_freed, \
                "release freed the wrong blocks (refcount drift)"
        stats = alloc.stats()
        assert stats["total"] == n_blocks
        assert stats["free"] + stats["in_use"] == n_blocks, \
            "HBM blocks not conserved"
        assert stats["host_free"] + stats["host_in_use"] == host_blocks, \
            "host blocks not conserved"
        hbm_refs = sum(1 for b in refs if b < n_blocks)
        assert stats["in_use"] == hbm_refs
        assert stats["host_in_use"] == len(refs) - hbm_refs
        assert stats["shared"] == sum(1 for c in refs.values() if c > 1)
        for b, c in refs.items():
            assert alloc.refcount(b) == c, "refcount drift"
            assert alloc.tier_of(b) == ("hbm" if b < n_blocks else "host")
        assert sum(alloc.free_in(g) for g in range(groups)) == stats["free"]
        for g in range(groups):
            # exact watermark model: the minimum free count since the
            # last epoch reset (free only dips within an op, so the
            # post-op value is the op's minimum)
            water[g] = min(water[g], alloc.free_in(g))
            assert alloc.low_water(g) == water[g], "watermark drift"
    return alloc, live, refs


@pytest.mark.parametrize("n_blocks,groups,host_blocks", POOL_GEOMETRIES)
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_block_allocator_churn_invariants(n_blocks, groups, host_blocks,
                                          seed):
    rng = random.Random(f"{n_blocks}/{groups}/{host_blocks}/{seed}")
    sub = n_blocks // groups
    ops = [(rng.random(), rng.randrange(groups),
            rng.randint(0, sub + 1),      # +1: requests past sub capacity
            rng.randrange(1 << 30)) for _ in range(400)]
    alloc, live, refs = _fuzz_allocator(n_blocks, groups, ops, sub,
                                        host_blocks)
    # drain: releasing every holder (aliases included) restores the
    # full pool — no leaks, no lingering refcounts, in either tier
    for got in live:
        alloc.release(got)
    assert alloc.release([]) == []        # empty release is a no-op
    assert alloc.stats() == {"total": n_blocks, "free": n_blocks,
                             "in_use": 0, "shared": 0, "groups": groups,
                             "host_total": host_blocks,
                             "host_free": host_blocks, "host_in_use": 0}


def test_block_allocator_rejects_bad_usage():
    with pytest.raises(ValueError, match="multiple"):
        BlockAllocator(10, 4)             # unequal sub-pools
    with pytest.raises(ValueError, match="groups"):
        BlockAllocator(8, 0)
    alloc = BlockAllocator(8, 2)
    got = alloc.allocate(2, group=1)
    assert got == [4, 5]                  # group 1 owns ids [4, 8)
    alloc.release(got)
    with pytest.raises(ValueError, match="double free"):
        alloc.release(got)                # already back in the pool
    with pytest.raises(ValueError, match="not currently allocated"):
        alloc.release([0])                # never handed out
    assert alloc.allocate(5, group=0) is None      # > sub-pool capacity
    assert alloc.stats()["free"] == 8


def test_block_allocator_refcount_lifecycle():
    """The sharing contract the prefix cache leans on: retain bumps,
    release decrements, and a block returns to its free list only when
    the LAST holder lets go — with misuse staying loud."""
    alloc = BlockAllocator(8, 2)
    got = alloc.allocate(2, group=0)
    assert [alloc.refcount(b) for b in got] == [1, 1]
    alloc.retain(got)                     # a second holder aliases both
    assert [alloc.refcount(b) for b in got] == [2, 2]
    assert alloc.stats()["shared"] == 2
    assert alloc.release(got) == []       # first holder: nothing freed
    assert alloc.stats()["in_use"] == 2   # still resident via the alias
    assert alloc.stats()["shared"] == 0
    assert sorted(alloc.release(got)) == sorted(got)   # last holder frees
    with pytest.raises(ValueError, match="double free"):
        alloc.release(got)
    with pytest.raises(ValueError, match="retain a free"):
        alloc.retain(got)                 # can't resurrect a freed block
    with pytest.raises(ValueError, match="retain a free"):
        alloc.retain([7])                 # never handed out
    assert alloc.refcount(5) == 0         # free blocks report zero
    # empty-sequence release is an explicit no-op, not an error
    assert alloc.release([]) == []
    assert alloc.stats() == {"total": 8, "free": 8, "in_use": 0,
                             "shared": 0, "groups": 2, "host_total": 0,
                             "host_free": 0, "host_in_use": 0}


def test_block_allocator_matches_engine_block_stats_contract():
    """The engine's block_stats() is exactly the allocator's stats():
    the keys the serving tests (and the churn invariants above) rely on
    are always present and always sum to n_blocks."""
    alloc = BlockAllocator(16, 2)
    a = alloc.allocate(3, 0)
    b = alloc.allocate(8, 1)
    s = alloc.stats()
    assert s["total"] == 16 and s["in_use"] == 11 and s["free"] == 5
    alloc.release(a + b)
    assert alloc.stats()["free"] == 16


def test_block_allocator_no_grant_after_free():
    """A released block sits in its free list until re-allocated — it
    is never still reachable through its previous holder.  Draining the
    sub-pool after a release must hand every id out exactly once."""
    alloc = BlockAllocator(8, 2)
    held = alloc.allocate(3, 0)
    freed = held.pop(1)
    alloc.release([freed])
    drained = []
    while True:
        blk = alloc.allocate_one(0)
        if blk is None:
            break
        drained.append(blk)
    # the freed block came back exactly once; the still-held ones never
    assert drained.count(freed) == 1
    assert not (set(drained) & set(held))
    assert sorted(drained + held) == list(range(4))   # group 0 = ids [0,4)
    assert alloc.free_in(0) == 0 and alloc.low_water(0) == 0


def test_block_allocator_low_water_tracks_minimum():
    alloc = BlockAllocator(8, 1)
    assert alloc.low_water() == 8
    a = alloc.allocate(5)
    assert alloc.low_water() == 3
    alloc.release(a)
    assert alloc.low_water() == 3, "watermark must survive the refill"
    b = alloc.allocate(7)
    assert alloc.low_water() == 1
    alloc.release(b)
    assert alloc.stats()["free"] == 8


def test_reset_low_water_starts_new_epoch():
    """The ratchet-forever fix: without an epoch reset, one transient
    dip pins the watermark for the allocator's whole lifetime and the
    engine's rebalancer reads a permanently hot sub-pool.  After
    ``reset_low_water()`` the mark reports only *this* epoch's minimum
    — and a promote's sub-pool draw dips it exactly like a grant."""
    alloc = BlockAllocator(8, 1, host_blocks=4)
    a = alloc.allocate(7)
    alloc.release(a)
    assert alloc.low_water() == 1         # the transient dip, ratcheted
    alloc.reset_low_water()
    assert alloc.low_water() == 8, "epoch reset must snap to current free"
    assert alloc.low_water_epochs == 1
    b = alloc.allocate(2)
    assert alloc.low_water() == 6         # this epoch's own minimum
    pairs = alloc.spill(b)
    assert alloc.free == 8                # spill returns the HBM ids…
    assert alloc.low_water() == 6         # …but never raises the mark
    got = alloc.promote([h for _, h in pairs], 0)
    assert alloc.low_water() == 6         # promote drew 2 of 8 again
    alloc.release([nb for _, nb in got])
    alloc.reset_low_water()
    assert alloc.low_water() == 8 and alloc.low_water_epochs == 2


def test_block_allocator_tier_transitions_reject_bad_usage():
    """Spill/promote misuse stays loud: wrong tier, non-resident ids,
    duplicates, and over-capacity moves all refuse instead of
    corrupting the accounting."""
    alloc = BlockAllocator(8, 2, host_blocks=4)
    got = alloc.allocate(3, group=0)
    pairs = alloc.spill(got[:2])
    host = [h for _, h in pairs]
    assert all(alloc.tier_of(h) == "host" for h in host)
    assert alloc.free_in(0) == 3          # the vacated ids came home
    with pytest.raises(ValueError, match="host-resident"):
        alloc.spill(host)                 # already host-tier
    with pytest.raises(ValueError, match="hbm-resident"):
        alloc.promote([got[2]], 0)        # still HBM-resident
    free_host = (set(range(8, 12)) - set(host)).pop()
    with pytest.raises(ValueError, match="not currently allocated"):
        alloc.promote([free_host], 0)     # never spilled into
    with pytest.raises(ValueError, match="listed twice"):
        alloc.promote([host[0], host[0]], 0)
    back = alloc.promote(host, 1)         # promote may target any group
    assert all(4 <= b < 8 for _, b in back), "promote missed its group"
    alloc.release([got[2]] + [b for _, b in back])
    assert alloc.stats()["free"] == 8
    assert alloc.stats()["host_free"] == 4
    with pytest.raises(ValueError, match="outside both tiers"):
        alloc.tier_of(12)
    with pytest.raises(ValueError, match="outside HBM pool"):
        alloc.group_of(8)                 # host ids have no group


if HAVE_HYPOTHESIS:
    @given(st.sampled_from(POOL_GEOMETRIES),
           st.lists(st.tuples(st.floats(0, 1), st.integers(0, 7),
                              st.integers(0, 12), st.integers(0, 1 << 20)),
                    min_size=1, max_size=300))
    @settings(max_examples=50, deadline=None)
    def test_block_allocator_churn_invariants_hypothesis(geom, raw_ops):
        n_blocks, groups, host_blocks = geom
        ops = [(k, g % groups, need, pick) for k, g, need, pick in raw_ops]
        alloc, live, refs = _fuzz_allocator(n_blocks, groups, ops,
                                            n_blocks // groups, host_blocks)
        for got in live:
            alloc.release(got)
        assert alloc.stats()["free"] == n_blocks
        assert alloc.stats()["host_free"] == host_blocks


# =====================================================================
# serving-engine churn fuzz: grow-on-demand grants, victim preemption,
# sub-pool migration, and shedding under a seeded chaotic workload —
# the engine-level invariants the allocator fuzz cannot see (token
# identity across evictions, the slot→sub-pool contract through
# migration, shed requests never holding blocks)
# =====================================================================

@pytest.mark.parametrize("seed", [0, 1])
def test_engine_churn_fuzz_grant_preempt_migrate(seed):
    """Grant-mode engine on a deliberately tight 2-sub-pool geometry,
    with injected grant denials AND random forced evictions: every
    request that finishes must be token-identical to its uninterrupted
    single-request run, every tick must conserve blocks and respect the
    slot→sub-pool contract, parked/shed requests must hold nothing, and
    the drained pool must be whole."""
    import jax
    from repro.configs import get_arch
    from repro.models import lm
    from repro.models.lm import RunCfg
    from repro.serve.engine import PreemptionPolicy, ServeEngine

    cfg = RunCfg(block_q=16, ssd_chunk=16)
    arch = get_arch("qwen3-8b").reduced()
    params = lm.init_params(arch, jax.random.PRNGKey(0))
    rng = random.Random(seed)
    prompts = [np.asarray([(i * 7 + j * 3 + 1) % arch.vocab_size
                           for j in range(plen)], np.int32)
               for i, plen in enumerate([5, 8, 11, 8, 5][:5])]
    new = 8
    want = []
    for p in prompts:
        e = ServeEngine(arch, params, cfg, max_batch=1, max_len=32)
        e.submit(p, max_new_tokens=new)
        want.append(e.run_until_idle(max_ticks=64)[0].out_tokens)

    eng = ServeEngine(arch, params, cfg, max_batch=4, max_len=32,
                      kv_residency="paged", kv_block_len=8, kv_n_blocks=8,
                      kv_admission="grant", kv_pool_groups=2,
                      preemption=PreemptionPolicy(max_preemptions=30,
                                                  backoff_base_ticks=1,
                                                  backoff_cap_ticks=4))
    eng.grant_fault = lambda: rng.random() < 0.2
    for p in prompts:
        eng.submit(p, max_new_tokens=new)
    ticks = 0
    while (eng.pending or eng.active or eng.preempted) and ticks < 600:
        if eng.active and (ticks == 3 or rng.random() < 0.05):
            # tick 3 guarantees >= 1 mid-decode eviction + re-prefill
            # even when migration absorbs every injected denial
            eng.preempt(rng.choice(list(eng.active.values())).rid)
        eng.step()
        ticks += 1
        stats = eng.block_stats()      # conservation asserts internally
        held = [b for r in eng.active.values() for b in r.blocks]
        assert len(held) == len(set(held)) == stats["in_use"], \
            "a block is held by two slots (or leaked)"
        for slot, r in eng.active.items():
            g = eng._slot_group(slot)
            assert all(eng._alloc.group_of(b) == g for b in r.blocks), \
                "slot -> sub-pool contract violated"
        for r in eng.shed:
            assert not r.blocks and r.error, "shed request holds blocks"
        for parked in eng.preempted:
            assert not parked.request.blocks, "parked eviction holds blocks"
    assert not (eng.pending or eng.active or eng.preempted), \
        "fuzz run did not drain"
    assert eng.preemptions >= 1, "churn never forced an eviction"
    assert len(eng.finished) + len(eng.shed) == len(prompts)
    got = {r.prompt.tobytes(): r.out_tokens for r in eng.finished}
    for p, w in zip(prompts, want):
        if p.tobytes() in got:
            assert got[p.tobytes()] == w, \
                "preempted request diverged from its uninterrupted run"
    assert eng.block_stats()["free"] == 8, "blocks leaked"


def test_engine_resets_low_water_epoch_per_rebalance_cycle():
    """The engine owns the epoch clock: once per shed window it calls
    ``reset_low_water()``, so a burst that drained a sub-pool early in
    an engine's life stops reading as permanent pressure.  Before the
    fix the watermark ratcheted down forever."""
    import jax
    from repro.configs import get_arch
    from repro.models import lm
    from repro.models.lm import RunCfg
    from repro.serve.engine import PreemptionPolicy, ServeEngine

    arch = get_arch("qwen3-8b").reduced()
    params = lm.init_params(arch, jax.random.PRNGKey(0))
    eng = ServeEngine(arch, params, RunCfg(block_q=16, ssd_chunk=16),
                      max_batch=2, max_len=32, kv_residency="paged",
                      kv_block_len=8, kv_n_blocks=4, kv_admission="grant",
                      preemption=PreemptionPolicy(shed_window_ticks=4))
    eng.submit(np.arange(11, dtype=np.int32) % arch.vocab_size,
               max_new_tokens=6)
    eng.run_until_idle(max_ticks=64)
    assert eng._alloc.low_water() < 4, "the burst never dipped the mark"
    dipped = eng._alloc.low_water()
    ticks = eng.tick
    while eng.tick < ticks + 8:           # two idle rebalance windows
        eng.step()
    assert eng._alloc.low_water_epochs >= 2
    assert eng._alloc.low_water() == 4, \
        f"watermark stuck at the historical dip ({dipped}) after the " \
        "rebalance epoch reset"


# =====================================================================
# pool-geometry invariants (the 2-D sharding contract the pass and the
# allocator both lean on) — seeded random, always runs
# =====================================================================

@pytest.mark.parametrize("seed", [0, 1])
def test_kv_block_geometry_2d_invariants(seed):
    rng = random.Random(seed)
    for _ in range(200):
        seq = rng.choice([64, 256, 1024, 4096, 32768])
        batch = rng.randint(1, 256)
        d = rng.choice([1, 2, 4, 8, 16])
        m = rng.choice([1, 2, 4, 8, 16])
        budget = rng.choice([None, 0.0, 2.0**rng.randint(20, 40)])
        geo = kv_block_geometry(seq, batch, 2 * 4 * 2 * 64 * 2,
                                budget_bytes=budget, data_shards=d, align=m)
        # the pool always splits into d equal, model-shardable sub-pools
        assert geo.n_blocks % d == 0
        sub = geo.n_blocks // d
        assert sub % m == 0
        # each sub-pool can always host at least one full sequence
        assert sub >= geo.blocks_per_seq
        # capacity never exceeds the worst case (every slot at max
        # depth) or the aligned one-sequence-per-sub-pool floor
        per = geo.blocks_per_seq
        floor_sub = m * math.ceil(per / m) if m > 1 else per
        assert geo.n_blocks <= max(batch * per, d * floor_sub)
        assert geo.data_degree == d and geo.sub_pool_blocks == sub


# =====================================================================
# wire-compression round-trip fuzz (the lowered train step's reduction
# primitive) + combine-topology dispatch invariants — seeded random,
# always runs
# =====================================================================

#: (r, shape): stacked-slice degrees x leaf shapes, covering last dims
#: below / at / straddling the 128-element quantization block, a
#: 1-element last dim, and a multi-axis leaf
SLICE_SHAPES = [(1, (5,)), (2, (1,)), (2, (127,)), (3, (128,)),
                (4, (129,)), (8, (300,)), (2, (3, 70)), (4, (2, 2, 40))]


@pytest.mark.parametrize("seed", [0, 1])
def test_compressed_slice_sum_roundtrip_invariants(seed):
    """The contracts the lowered wire step leans on, fuzzed over shapes
    and scales: shape/dtype preservation, the telescoping identity
    ``mean + mean_i(err_i) == mean_i(x_i)`` (exact up to f32 rounding),
    the per-block error bound ``|err| <= amax_block / 254`` with the
    scale shared across slices, and the degenerates — an all-zero
    stack round-trips to exact zeros, and a single slice (r=1)
    reconstructs exactly via its own residual."""
    from repro.dist.collectives import BLOCK, compressed_slice_sum
    rng = np.random.default_rng(seed)
    for r, shape in SLICE_SHAPES:
        x = (rng.standard_normal((r,) + shape)
             * 10.0 ** rng.integers(-3, 3)).astype(np.float32)
        mean, err = compressed_slice_sum(jnp.asarray(x))
        assert mean.shape == shape and mean.dtype == jnp.float32
        assert err.shape == x.shape and err.dtype == jnp.float32
        m, e = np.asarray(mean), np.asarray(err)
        # telescoping identity, per element
        scale = max(np.abs(x).max(), 1.0)
        assert np.abs((m + e.mean(0)) - x.mean(0)).max() < 1e-6 * scale
        # per-block bound with the SHARED scale: amax over all slices
        d = x.shape[-1]
        pad = (-d) % BLOCK
        xp = np.concatenate(
            [x, np.zeros(x.shape[:-1] + (pad,), np.float32)], -1)
        blocks = xp.reshape(x.shape[:-1] + (-1, BLOCK))
        amax = np.abs(blocks).max(axis=-1).max(axis=0)   # shared over r
        ep = np.concatenate(
            [e, np.zeros(x.shape[:-1] + (pad,), np.float32)], -1)
        eb = np.abs(ep.reshape(x.shape[:-1] + (-1, BLOCK))).max(axis=-1)
        assert (eb <= amax[None] / 254.0 * 1.001 + 1e-9).all()
        if r == 1:
            # one slice: mean + err IS the input, exactly
            assert np.array_equal(m + e[0], x[0])
    # all-zero stack: codes are zero, scale floor never injects noise
    mean, err = compressed_slice_sum(jnp.zeros((4, 200), jnp.float32))
    assert not np.asarray(mean).any() and not np.asarray(err).any()


def test_compressed_slice_sum_matches_compressed_psum_degenerate():
    """r=1 slice sum == a 1-shard compressed_psum: the GSPMD twin and
    the shard_map primitive share one quantization recipe (a drift
    between them would silently change the wire semantics when the
    lowering gate flips between the two paths)."""
    import jax
    from jax.sharding import PartitionSpec as P
    from repro.dist.collectives import compressed_psum, compressed_slice_sum
    x = jnp.asarray(np.random.default_rng(3).standard_normal((3, 200)),
                    jnp.float32)
    mesh = make_mesh((1,), ("data",))
    y1, e1 = jax.jit(jax.shard_map(
        lambda v: compressed_psum(v, "data"), mesh=mesh,
        in_specs=P(), out_specs=(P(), P())))(x)
    # jit both: op-by-op dequant rounds differently from the fused form
    y2, e2 = jax.jit(compressed_slice_sum)(x[None])
    assert np.array_equal(np.asarray(y1), np.asarray(y2))
    assert np.array_equal(np.asarray(e1), np.asarray(e2[0]))


def test_combine_topology_choice_is_a_total_order():
    """The calibrated thresholds induce a monotone map from model
    degree to topology rank (flat < ring < bidir): once the degree is
    large enough to leave a topology behind, no larger degree ever
    returns to it — the property that makes the plan decision stable
    under mesh growth."""
    from repro.core.costmodel import (COMBINE_BIDIR_DEGREE,
                                      COMBINE_RING_DEGREE,
                                      COMBINE_TOPOLOGIES,
                                      COMBINE_TOPOLOGY_RANK, combine_hops,
                                      choose_combine_topology)
    prev = 0
    for n in range(1, 65):
        topo = choose_combine_topology(n)
        assert topo in COMBINE_TOPOLOGIES
        rank = COMBINE_TOPOLOGY_RANK[topo]
        assert rank >= prev, (n, topo)
        prev = rank
    # the calibrated boundaries themselves
    assert choose_combine_topology(COMBINE_RING_DEGREE) == "flat"
    assert choose_combine_topology(COMBINE_RING_DEGREE + 1) == "ring"
    assert choose_combine_topology(COMBINE_BIDIR_DEGREE) == "ring"
    assert choose_combine_topology(COMBINE_BIDIR_DEGREE + 1) == "bidir"
    # hop counts: the latency-model ordering behind the thresholds
    for n in range(2, 65):
        assert combine_hops(n, "flat") == 6 * (n - 1)
        assert combine_hops(n, "ring") == n - 1
        assert combine_hops(n, "bidir") == (n - 1 + 1) // 2
        assert combine_hops(n, "bidir") <= combine_hops(n, "ring") \
            < combine_hops(n, "flat")
    for t in ("flat", "ring", "bidir"):
        assert combine_hops(1, t) == 0    # no cross-shard combine exists
    with pytest.raises(ValueError, match="topology"):
        combine_hops(4, "hypercube")


def test_combine_topology_dispatch_agreement_single_process():
    """Kernel predicate and engine agree off-mesh: a degenerate model
    axis reports "flat" regardless of the override (no combine exists
    to re-route), and a single-process engine — whose decode path is
    not shard_map — reports "flat" in telemetry even when its RunCfg
    pins "ring" (the plan override only binds where the sharded combine
    actually runs)."""
    import jax
    from repro.configs import get_arch
    from repro.dist.flash_decode import combine_topology
    from repro.models import lm
    from repro.models.lm import RunCfg
    from repro.serve.engine import ServeEngine

    mesh1 = make_mesh((1,), ("model",))
    assert combine_topology(mesh1) == "flat"
    assert combine_topology(mesh1, override="bidir") == "flat"
    # the degenerate short-circuit wins even over a bogus override: no
    # combine exists to mis-route (the ValueError on real model axes is
    # pinned by the 8-device matrix test in test_multidevice)
    assert combine_topology(mesh1, override="hypercube") == "flat"
    # a mesh without the model axis at all is the same degenerate case
    assert combine_topology(make_mesh((1,), ("data",))) == "flat"

    arch = get_arch("qwen3-8b").reduced()
    params = lm.init_params(arch, jax.random.PRNGKey(0))
    eng = ServeEngine(arch, params,
                      RunCfg(block_q=16, ssd_chunk=16,
                             combine_topology="ring"),
                      max_batch=1, max_len=32)
    assert eng.decode_path not in ("shard_map_flash",
                                   "shard_map_flash_paged_2d")
    assert eng.combine_topology == "flat"
    assert eng.telemetry()["combine_topology"] == "flat"


# =====================================================================
# hypothesis tier (skipped cleanly when hypothesis is unavailable)
# =====================================================================

if HAVE_HYPOTHESIS:
    @given(st.lists(st.integers(1, 4096), min_size=1, max_size=4),
           st.data())
    @settings(max_examples=200, deadline=None)
    def test_resolve_pspec_always_divides(shape, data):
        axes = tuple(data.draw(st.sampled_from(AXIS_NAMES))
                     for _ in shape)
        spec = resolve_pspec(RULES, shape, axes, SIZES)
        used = set()
        for dim, s in zip(shape, tuple(spec) + (None,) * len(shape)):
            if s is None:
                continue
            names = (s,) if isinstance(s, str) else tuple(s)
            f = 1
            for n in names:
                assert n not in used      # a mesh axis shards one dim only
                used.add(n)
                f *= SIZES[n]
            assert dim % f == 0           # divisibility repair worked

    @given(st.lists(st.floats(-1e4, 1e4, allow_nan=False, width=32),
                    min_size=1, max_size=2048))
    @settings(max_examples=100, deadline=None)
    def test_int8_quantization_error_bound(vals):
        x = jnp.asarray(np.array(vals, np.float32))
        q, s, pad = quantize_int8(x)
        xr = dequantize_int8(q, s, pad, x.shape)
        # per-block error bounded by scale/2 = amax/254
        blocks = np.asarray(jnp.abs(x)).reshape(-1)
        bound = max(blocks.max() / 254.0, 1e-6) * 1.001
        assert float(jnp.abs(xr - x).max()) <= bound + 1e-6

    @given(st.lists(st.floats(-100, 100, allow_nan=False, width=32),
                    min_size=8, max_size=256),
           st.integers(2, 10))
    @settings(max_examples=50, deadline=None)
    def test_error_feedback_preserves_sum(vals, steps):
        """Sum of delivered values + residual == sum of inputs."""
        x = jnp.asarray(np.array(vals, np.float32))
        err = None
        delivered = jnp.zeros_like(x)
        for _ in range(steps):
            xh, err = ef_compress(x, err)
            delivered = delivered + xh
        total_in = float(jnp.sum(x)) * steps
        total_out = float(jnp.sum(delivered)) + float(jnp.sum(
            err.astype(jnp.float32)))
        scale = max(abs(total_in), 1.0)
        assert abs(total_in - total_out) / scale < 0.02

    @given(st.integers(1, 6),
           st.lists(st.floats(-1e3, 1e3, allow_nan=False, width=32),
                    min_size=1, max_size=512))
    @settings(max_examples=50, deadline=None)
    def test_compressed_slice_sum_telescopes_hypothesis(r, vals):
        """Hypothesis twin of the seeded round-trip fuzz: the
        telescoping identity holds for every stack degree and leaf the
        wire step could see."""
        from repro.dist.collectives import compressed_slice_sum
        base = np.asarray(vals, np.float32)
        x = jnp.asarray(np.stack([np.roll(base, i) for i in range(r)]))
        mean, err = compressed_slice_sum(x)
        lhs = np.asarray(mean) + np.asarray(err).mean(0)
        rhs = np.asarray(x).mean(0)
        scale = max(float(np.abs(base).max()), 1.0)
        assert np.abs(lhs - rhs).max() < 1e-6 * scale

    @given(st.integers(1, 100_000), st.integers(2, 64))
    @settings(max_examples=100, deadline=None)
    def test_ring_collective_inequalities(nbytes, n):
        ar = allreduce_bytes(nbytes, n)
        rs = reduce_scatter_bytes(nbytes, n)
        ag = allgather_bytes(nbytes, n)
        assert abs(ar - (rs + ag)) < 1e-6     # AR = RS + AG (ring identity)
        assert 0 <= rs < nbytes

    @given(st.integers(1, 65536), st.integers(1, 128), st.integers(1, 8),
           st.floats(1.0, 2.0))
    @settings(max_examples=100, deadline=None)
    def test_moe_capacity_sane(tokens, experts, topk, cf):
        c = _capacity(tokens, experts, topk, cf)
        assert c >= 4 and c % 4 == 0
        # enough capacity for a perfectly balanced router
        assert c * experts >= min(tokens * topk, 4 * experts) * 0.99

    @given(st.integers(1, 8), st.integers(1, 8), st.integers(1, 8))
    @settings(max_examples=50, deadline=None)
    def test_mesh_model_device_count(a, b, c):
        m = MeshModel(axes=("pod", "data", "model"), shape=(a, b, c))
        assert m.n_devices == a * b * c
        assert m.axis_size("data") == b
        assert m.axis_size(None) == 1
