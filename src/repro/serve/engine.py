"""Batched serving engine: continuous batching over prefill + decode.

The session cache is the template's ``cache.kv`` component: allocated
once at engine start (shape from the plan), slots assigned to requests,
freed on completion — residency management, not reallocation.

Scheduling: waiting requests are admitted in same-length buckets — every
pending prompt of the head-of-line length that fits a free slot (and,
when paged, the block pool) is prefilled in ONE jitted call — then every
engine tick decodes one token for all active slots.  Positions are
**per slot** (``cache["pos"]`` is ``(B,)``): a continuous batch mixes
prompt lengths, so each slot appends KV and masks attention at its own
offset — an engine-global scalar position silently corrupts every slot
whose length differs from the batch max.  Freed slots are masked to
``(token 0, pos 0)`` so their stale KV never flows into a live decode.
Greedy or temperature sampling; sampling threads one engine PRNG key
(``seed=``), split per tick and per slot, so runs are reproducible and
slots never share a key within a tick.  A tick samples every slot in
one program (``serve_sample``) and one transfer of the tokens to the
host; an engine whose ``_sample`` is overridden (a probe) samples row
by row through it instead.

KV residency is a plan decision (``kv_residency`` in the artifact):
``dense`` keeps the classic per-slot ``max_len`` stripes; ``paged``
allocates a block pool (``lm.init_paged_cache``) whose geometry the
data-organization pass chose, and *returns blocks to the pool on
finish* — real reclamation, so slot churn frees memory instead of
leaving masked rows resident.  On a data×model mesh the pool is 2-D
sharded (block dim data-major over both axes, batch slots partitioned
across data — ``dist.flash_decode.pool_sharding_kind``), so the
allocator works over *per-data-shard sub-pools*
(``serve.allocator.BlockAllocator``): a slot may only hold blocks from
the sub-pool of the data shard hosting it, because a foreign block
would be owned by no shard in the slot's data row and mask out of the
combine.

Admission is a plan decision too (``kv_admission``): ``reserve`` hands
an admitted request its full worst-case block budget up front (grants
can never fail mid-decode, but the pool pins bytes long-tail requests
never touch); ``grant`` is grow-on-demand — admission reserves only the
prompt's blocks and a slot asks for its next block when decode crosses
a block boundary.  Under ``grant`` exhaustion is a *handled* condition,
degraded through three rungs instead of a serialization cliff:

1. **grant** from the slot's own sub-pool;
2. **migrate** — when the home sub-pool is hot but another idles (and
   hosts a free slot), the slot's blocks, table row, and per-slot
   states move to the donor sub-pool, preserving the slot→sub-pool
   combine contract;
3. **preempt** — a victim (fewest-tokens-generated first,
   deadline-aware) is evicted to a host-side
   :class:`PreemptedRequest` — tokens generated so far retained — and
   re-admitted later via re-prefill of prompt+generated, with
   exponential backoff and a per-request retry budget (the
   :class:`repro.runtime.fault.RestartPolicy` shape, in ticks).

Past the retry budget (or a missed deadline) the request is *shed*
(``Request.error`` set, blocks released) rather than thrashed forever;
once the recent preemption rate crosses the policy threshold,
``submit()`` rejects new work with a typed :class:`OverloadError`
instead of hanging the admission queue.  Preemption is token-identical
for greedy sampling: a preempted-then-re-prefilled request emits
exactly the tokens of an uninterrupted run (the re-prefill rebuilds the
same KV rows; the discarded prefill sample is the token the host
already holds).

Paged residency additionally enables **cross-request prefix reuse**
(``kv_prefix_reuse``, a plan decision): every full ``block_len`` chunk
of an admitted feed is chain-hashed (:mod:`repro.serve.prefix_cache`)
and matched against a per-sub-pool radix trie of resident blocks.
Matched blocks are *aliased* into the new request's table with a
refcount bump (``BlockAllocator.retain``) instead of re-prefilled:
attention-only archs skip the matched tokens' prefill compute entirely
(a tail-only forward, :func:`repro.models.lm.prefill_tail`; a request
whose whole feed-but-last is matched rides the decode path with zero
prefill calls), while hybrid archs still prefill the full feed (their
SSM states need every token) but share the matched blocks' capacity.
Writers never mutate shared state: a copy-on-write barrier before each
decode tick copies any shared append block into a freshly granted one
(one jitted gather-scatter of the block's rows in every cache pool plus
the table entry).  The
degradation ladder is sharing-aware — migration refuses to move shared
blocks and victim selection prefers requests pinning the fewest —
and trie entries are pruned exactly when their blocks return to the
free list.

Residency is **multi-tier** when the plan sized a host spill pool
(``kv_tier_split`` / ``kv_host_blocks``): behind the HBM block pool
sits a host-DRAM pool (:func:`repro.models.lm.init_host_pool`, plain
numpy — host memory by construction) and every block carries an
explicit tier (``BlockAllocator.tier_of``).  Three mechanisms ride on
it:

1. **Cold-block spill.**  Blocks that would be freed but are still
   prefix-trie-indexed are retained as a block *cache* (refcount held
   by the engine); under low-water pressure the spill scheduler moves
   them to the host tier — and drops them only when the host pool is
   full too — so the reclaim ladder gets a rung *before* grant →
   migrate → preempt → shed ever fires.  Trie entries survive the
   spill tier-tagged (``PrefixCache.rekey``): a prefix hit on a
   spilled block **promotes** it back into the slot's sub-pool instead
   of missing.
2. **Park-with-state.**  Preemption's host-side park is unified with
   the tier: a victim's KV blocks spill to host (and its SSM/conv
   rows are saved host-side) instead of being discarded, so
   re-admission *promotes the blocks back and skips re-prefill
   entirely* — token-identical resume with zero recompute.  Shared
   blocks pin a victim in the legacy path (release + re-prefill):
   sharers' tables point at the old ids.
3. **Async prefetch.**  Re-admission is known one tick ahead (the
   backoff expiry), so the engine stages the host->device transfer
   (``jax.device_put``) for tick ``T`` during tick ``T-1`` — double
   buffered: the decode of one tick overlaps the stream-in for the
   next, keyed off the parked slot's next block-boundary crossing.
   With ``kv_prefetch="off"`` the transfer happens synchronously at
   resume (the stall the benchmark rows measure).

With tiering off (``kv_host_blocks=0``, the default) every path keeps
its exact pre-tier semantics.

Every block mover — spill, promote, prefetch, copy-on-write, prefix
alias, migration, park and the disaggregated chunk stream — carries
whatever pools the cache kind holds (:func:`repro.models.lm.cache_rows`:
per-head ``k``/``v``, or latent attention's one ``ckv`` row a token).

Prefill itself is a plan decision (``kv_prefill_mode``): when the
interference model says a worst-case inline prefill would steal too
many decode ticks, the engine runs **disaggregated** — prompts
dispatch to a supervised worker fleet (:mod:`repro.serve.disagg`)
that prefills them chunked block-native and streams pool-block-shaped
KV slabs back; the engine scatters each arriving block into the paged
pool (the spill path run in reverse) and decode never waits on a
prompt.  In-flight requests hold their slot and blocks but keep the
block-table row at -1 until completion, so the freed-slot dummy
decode can never touch a half-written block.  Acked full blocks form
an idempotent journal: when a worker dies mid-prompt the request
re-dispatches *from the last acked block boundary* (the journaled
rows are gathered back as the resume prefix — token-identical by the
``attention_tail`` bitwise contract).  When the fleet exhausts its
restart budget the engine degrades to in-process prefill under a
typed :class:`~repro.serve.disagg.DegradedMode` — never an unhandled
crash — and deadline/overload semantics compose with the shed ladder
unchanged (a request sheds the same way whether it dies in prefill or
decode).

Engines are plan-driven: :meth:`ServeEngine.from_plan` consumes the
frozen plan artifact the specialization flow produced (possibly reloaded
from the on-disk plan store in a different process) and derives the KV
cache sizing, decode implementation, admission mode, and batching
limits from it — no ad-hoc kwargs needed between the compiler and the
server.  With a ``mesh`` the engine state is *placed* per the plan's
axis rules (``dist.sharding.resolve_pspec``/``cache_pspecs``) and a
plan that chose the seq-sharded ``shard_map_flash`` decode drives it
end-to-end — no silent XLA fallback.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Callable, Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig, get_arch
from repro.models import lm
from repro.models.lm import RunCfg
from repro.runtime.fault import RestartPolicy
from repro.runtime.trace import span
from repro.serve.prefix_cache import PrefixCache, chain_hashes


def _refuse_disagg_on_tpu() -> None:
    """Prefill workers are spawned processes; on a TPU host this process
    holds the chips, so a worker would prefill on the host CPU."""
    if jax.default_backend() == "tpu":
        raise RuntimeError(
            "kv_prefill_mode='disagg' is refused on a TPU backend: the "
            "prefill workers would have no chip of their own and would "
            "prefill on the host CPU; serve with inline prefill")


class OverloadError(RuntimeError):
    """The engine is shedding load: the recent preemption rate crossed
    the policy threshold, so new admissions would only thrash the pool
    (evict work that re-prefills and evicts the next victim).  Callers
    should back off and retry, or route to another replica — the typed
    rejection is the graceful-degradation contract: reject loudly at
    the door instead of hanging every queued request."""


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray             # (prompt_len,) int32
    max_new_tokens: int = 16
    temperature: float = 0.0
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    slot: int = -1
    blocks: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    t_submit: float = 0.0
    t_first: float = 0.0
    t_done: float = 0.0
    deadline: Optional[float] = None   # absolute wall-clock deadline
    preemptions: int = 0               # times evicted mid-decode
    error: str = ""                    # set when shed (never finished)
    # chain hashes of the feed's full blocks at last (re-)admission —
    # migration re-registers the moved blocks under these
    prefix_hashes: List[str] = dataclasses.field(default_factory=list)

    @property
    def feed_tokens(self) -> np.ndarray:
        """The token sequence a (re-)prefill must build KV for: the
        prompt, plus — after a preemption — every generated token except
        the last (whose KV row does not exist yet; the next decode tick
        feeds it)."""
        if not self.out_tokens:
            return self.prompt
        return np.concatenate(
            [self.prompt, np.asarray(self.out_tokens[:-1], np.int32)])


@dataclasses.dataclass
class PreemptionPolicy:
    """How the engine degrades when a mid-decode block grant fails.

    Victim choice is fewest-tokens-generated first (least re-prefill
    work thrown away) and deadline-aware: requests carrying a deadline
    are spared while any deadline-free victim exists, and among
    deadline'd candidates the latest deadline goes first.  Backoff and
    retry budget reuse the :class:`repro.runtime.fault.RestartPolicy`
    shape, measured in engine ticks (the serving clock) instead of
    seconds.
    """

    max_preemptions: int = 4          # per-request retry budget
    backoff_base_ticks: int = 1       # first re-admission delay
    backoff_cap_ticks: int = 32       # exponential backoff ceiling
    shed_window_ticks: int = 64       # sliding window for the rate
    shed_rate: float = 0.5            # preemptions/tick that means overload

    def restart_policy(self) -> RestartPolicy:
        return RestartPolicy(max_restarts=self.max_preemptions,
                             backoff_base_s=float(self.backoff_base_ticks),
                             backoff_cap_s=float(self.backoff_cap_ticks))

    def pick_victim(self, candidates: List[Request],
                    now: float) -> Request:
        def key(r: Request):
            if r.deadline is None:
                return (0, len(r.out_tokens), 0.0, r.rid)
            # spare deadline'd requests; among them evict latest-deadline
            return (1, len(r.out_tokens), -(r.deadline - now), r.rid)
        return min(candidates, key=key)


@dataclasses.dataclass
class PreemptedRequest:
    """Host-side parking spot for an evicted request: the tokens
    generated so far stay on the request; its KV is rebuilt by
    re-prefill at ``not_before_tick`` (exponential backoff) — unless
    ``parked_state`` is set (tiered park): then the KV blocks live on
    in the host tier (ids in ``request.blocks``) with SSM/conv rows
    saved alongside, and re-admission promotes instead of
    re-prefilling."""

    request: Request
    not_before_tick: int
    # tiered park: {"slot_len": int, "kv_host": [host ids]} for paged
    # KV, {"kv_rows": {pool: rows}} for dense stripes, plus "ssm"/"conv"
    # host copies when the arch carries them
    parked_state: Optional[Dict[str, Any]] = None


@dataclasses.dataclass
class _DisaggFlight:
    """A request whose prefill is out at the worker fleet.  It owns a
    slot and its admission blocks, but the block-table row stays -1
    until completion (the freed-slot dummy decode must never append
    into a half-written block).  ``acked`` counts contiguous *full*
    blocks scattered into the pool — the idempotent journal a
    re-dispatch resumes from after a worker death."""

    request: Request
    slot: int
    group: int
    nb_feed: int                   # ceil(flen / block_len)
    acked: int = 0


class ServeEngine:
    def __init__(self, arch: ArchConfig, params, cfg: RunCfg,
                 max_batch: int = 8, max_len: int = 512,
                 ssm_heads: int = 0, kv_heads: int = 0, seed: int = 0,
                 kv_residency: str = "dense", kv_block_len: int = 0,
                 kv_n_blocks: int = 0, kv_admission: str = "reserve",
                 kv_pool_groups: int = 0, kv_prefix_reuse: str = "on",
                 kv_host_blocks: int = 0, kv_prefetch: str = "on",
                 preemption: Optional[PreemptionPolicy] = None):
        if kv_admission not in ("reserve", "grant"):
            raise ValueError(
                f"kv_admission must be 'reserve' or 'grant', "
                f"got {kv_admission!r}")
        if kv_prefix_reuse not in ("on", "off"):
            raise ValueError(
                f"kv_prefix_reuse must be 'on' or 'off', "
                f"got {kv_prefix_reuse!r}")
        if kv_prefetch not in ("on", "off"):
            raise ValueError(
                f"kv_prefetch must be 'on' or 'off', got {kv_prefetch!r}")
        if kv_host_blocks < 0:
            raise ValueError(
                f"kv_host_blocks must be >= 0, got {kv_host_blocks}")
        self.arch, self.params, self.cfg = arch, params, cfg
        self.plan = None               # set by from_plan()
        # the cache pools every block mover carries (k/v per kv head,
        # or MLA's latent row): ``lm.cache_rows`` names them
        self._pools = tuple(lm.cache_rows(arch, kv_heads))
        self.max_batch, self.max_len = max_batch, max_len
        self.kv_admission = kv_admission
        self.preemption = preemption or PreemptionPolicy()
        # paged residency only exists for attention caches; an SSM-only
        # arch has no KV stripes to page (its states are O(1) in seq)
        self.kv_residency = ("paged" if kv_residency == "paged"
                             and arch.has_attention else "dense")
        if self.kv_residency == "paged":
            import math
            from repro.core.costmodel import kv_block_len as _default_bl
            from repro.serve.allocator import BlockAllocator
            self.block_len = kv_block_len or _default_bl(max_len)
            per_seq = -(-max_len // self.block_len)
            # never larger than this engine's slots can ever pin (a plan
            # sized for a bigger deployment must not balloon a small one);
            # a plan-shrunk (budget-capped) pool stays shrunk
            cap = max_batch * per_seq
            n = min(kv_n_blocks, cap) if kv_n_blocks else cap
            groups = 1
            if cfg.mesh is not None:
                # preserve the plan's pool divisibility through the
                # clamp: a clamp that breaks it would silently downgrade
                # the pool-sharded decode (2-D -> 1-D -> single-shard)
                # AND replicate the pool on the broken axis
                from repro.dist.flash_decode import pool_sharding_kind
                from repro.dist.sharding import mesh_sizes
                sizes = mesh_sizes(cfg.mesh)
                msize = sizes.get(cfg.model_axis, 1)
                dsize = math.prod(sizes.get(a, 1) for a in cfg.data_axes)
                aligns = []
                if dsize > 1 and max_batch % dsize == 0:
                    aligns.append(dsize * msize)
                if msize > 1:
                    aligns.append(msize)
                for align in aligns:
                    if align > 1 and n % align and \
                            (not kv_n_blocks or kv_n_blocks % align == 0):
                        n = align * (-(-n // align))
                        if kv_n_blocks:
                            n = min(kv_n_blocks, n)
                        break
                # sub-pool grouping exists for the 2-D combine's
                # ownership contract; other decode impls (xla gather)
                # read any block from anywhere, so constraining their
                # admission would refuse servable requests
                if cfg.decode_impl == "shard_map_flash" and \
                        pool_sharding_kind(cfg.mesh, n, max_batch,
                                           cfg.data_axes,
                                           cfg.model_axis) == "2d":
                    groups = dsize
            if kv_pool_groups:
                # explicit grouping: single-host emulation of the 2-D
                # sub-pool contract (tests, diagnostics) — the slot→
                # sub-pool mapping needs equal slot ranges per group
                if n % kv_pool_groups or max_batch % kv_pool_groups:
                    raise ValueError(
                        f"kv_pool_groups={kv_pool_groups} must divide both "
                        f"n_blocks={n} and max_batch={max_batch}")
                groups = kv_pool_groups
            self.n_blocks = n
            self.pool_groups = groups
            # host spill tier (the plan's kv_tier_split): a second pool
            # of host-DRAM blocks behind the HBM pool.  Clamped like
            # n_blocks — a plan sized for a bigger deployment must not
            # balloon a small engine's host pin — to a park depth of 8
            # full worst-case batches (past that, parked sessions wait
            # on slots, not on host bytes).
            self.host_blocks = min(kv_host_blocks, 8 * cap) \
                if kv_host_blocks > 0 else 0
            self.cache = lm.init_paged_cache(
                arch, max_batch, max_len, self.block_len, self.n_blocks,
                ssm_heads=ssm_heads, kv_heads=kv_heads)
            self._alloc = BlockAllocator(self.n_blocks, groups,
                                         host_blocks=self.host_blocks)
            self._host = (lm.init_host_pool(arch, self.host_blocks,
                                            self.block_len,
                                            kv_heads=kv_heads)
                          if self.host_blocks else None)
            # cross-request prefix reuse: one trie per sub-pool (a match
            # in a foreign sub-pool would break the combine contract)
            self.kv_prefix_reuse = kv_prefix_reuse == "on"
            self._prefix: Optional[PrefixCache] = (
                PrefixCache(groups) if self.kv_prefix_reuse else None)
        else:
            from repro.serve.allocator import BlockAllocator
            self.block_len = 0
            self.n_blocks = 0
            self.host_blocks = 0
            self.pool_groups = 1
            self.cache = lm.init_cache(arch, max_batch, max_len,
                                       ssm_heads=ssm_heads, kv_heads=kv_heads)
            self._alloc = BlockAllocator(0, 1)
            self._host = None
            self.kv_prefix_reuse = False
            self._prefix = None
        # tiered residency: host-side park of KV blocks (paged), dense
        # stripes, and SSM/conv rows — enables no-re-prefill resume.
        # Dense/SSM engines park per-slot state without a block pool.
        self.kv_tiering = kv_host_blocks > 0
        self.kv_prefetch = kv_prefetch == "on"
        # engine-held block cache: blocks a release would have freed
        # but that the prefix trie still indexes (refcount 1, held
        # here).  Insertion-ordered — the spill scheduler works
        # oldest-first.  Only populated with tiering on.
        self._cached: Dict[int, None] = {}
        # prefetch staging: rid -> (host id tuple, {pool: rows}) put on
        # device one tick before the parked request's re-admission
        self._staged: Dict[int, Any] = {}
        # admission-scoped map of promoted ids (old host id -> new HBM
        # id) so a second request matching the same just-promoted block
        # follows it instead of aliasing a freed host id
        self._promo_map: Dict[int, int] = {}
        # matched tokens' prefill compute is only skippable when the
        # whole per-token state is attention KV; an SSM/hybrid state
        # depends on every prefix token, so those archs alias blocks
        # (capacity sharing) but still prefill the full feed
        self._skip_prefix = (self._prefix is not None
                             and arch.has_attention and not arch.has_ssm)
        self.free_slots = list(range(max_batch))
        self.active: Dict[int, Request] = {}
        self.pending: List[Request] = []
        self._rid = 0
        self.finished: List[Request] = []
        # overload-degradation state: host-side parked evictions, shed
        # requests (never finished; Request.error says why), per-request
        # backoff budgets, and the sliding preemption-rate window
        self.preempted: List[PreemptedRequest] = []
        self.shed: List[Request] = []
        self._backoff: Dict[int, RestartPolicy] = {}
        self._preempt_ticks: Deque[int] = deque(maxlen=4096)
        self.tick = 0
        self.preemptions = 0
        self.migrations = 0
        self.grant_denials = 0
        # chaos/test hook: return True to deny one mid-decode grant even
        # when blocks are free (drives the preemption path exactly like
        # a hot sub-pool would; see scripts/serve_smoke.py --chaos)
        self.grant_fault: Optional[Callable[[], bool]] = None
        # per-slot valid lengths; mirrored into cache["pos"] every tick
        # (freed slots stay at 0 — their stale KV is masked out)
        self.slot_len = np.zeros((max_batch,), np.int32)
        self._key = jax.random.PRNGKey(seed)
        self._pos_sharding = None      # set by _place_on_mesh()
        # admission telemetry: bucketed prefill batch sizes per call
        # (bounded — long-running engines must not accumulate history)
        self.prefill_calls = 0
        self.prefill_batches: Deque[int] = deque(maxlen=1024)
        # prefix-sharing telemetry (hit/miss counters live on _prefix)
        self.cow_copies = 0
        self.prefix_rides = 0          # admissions with zero prefill calls
        # disaggregated prefill (attach_fleet() flips the mode on):
        # rid -> in-flight dispatch; acked full blocks are the
        # idempotent re-dispatch journal
        self.kv_prefill_mode = "inline"
        self._fleet = None
        self._disagg: Dict[int, _DisaggFlight] = {}
        self._redispatch: List[int] = []
        self._inline_poison: set = set()   # rids whose worker prefill raised
        self.degraded = None               # disagg.DegradedMode once degraded
        self.disagg_dispatches = 0
        self.disagg_chunks = 0
        self.disagg_resumes = 0
        # rows drawn by the batched program / by per-row ``_sample``
        self.sampled_batched = 0
        self.sampled_per_slot = 0
        # paged decode: the busy slots' live blocks the ticks read, and
        # the table entries (max_batch x columns) those ticks addressed
        self.decode_blocks_live = 0
        self.decode_blocks_table = 0
        # token-expert pairs the held-expert layer computed, by phase
        # (the model programs' ``moe_held_pairs`` counter)
        self.moe_held_pairs = {"prefill": 0, "decode": 0}

        # each program is a named function, so that its module in a
        # profiler trace reads jit_serve_<name>; each model program also
        # returns its counters (``lm.decode_step``'s ``stats``)
        def serve_decode(p, c, b):
            return lm.decode_step(arch, p, c, b, cfg, stats=True)

        def serve_prefill(p, b):
            return lm.prefill(arch, p, b, cfg, max_len=max_len, stats=True)

        def serve_prefill_tail(p, b, pre):
            return lm.prefill_tail(arch, p, b, cfg, pre, stats=True)

        def serve_cow(pools, tbl, old, new, slot, col):
            """CoW: duplicate one block's rows in every cache pool and
            repoint one table entry, in a single gather-scatter."""
            return ({k: v.at[:, new].set(v[:, old])
                     for k, v in pools.items()},
                    tbl.at[slot, col].set(new))

        # tier migration: batched whole-block gather/scatter between
        # the device pool and the host spill pool
        def serve_gather_blocks(pool, ids):
            return lm.gather_blocks(pool, ids)

        def serve_scatter_blocks(pool, ids, rows):
            return lm.scatter_blocks(pool, ids, rows)

        def serve_sample(logits, temps, keys, sampled, counts=None):
            """Every row's next token by ``_sample``'s rule: argmax where
            the row's temperature is <= 0, a categorical draw with its
            key where it is > 0.  ``sampled`` (static) is False when no
            row draws, so a greedy batch pays for no Gumbel noise.
            ``counts`` (per-row int32 counters of the program sampled
            from) ride home in the same transfer, stacked under the
            tokens."""
            rows = logits[:, :arch.vocab_size].astype(jnp.float32)
            toks = jnp.argmax(rows, axis=-1).astype(jnp.int32)
            if sampled:
                hot = temps > 0
                drawn = jax.vmap(jax.random.categorical)(
                    keys, rows / jnp.where(hot, temps, 1.0)[:, None])
                toks = jnp.where(hot, drawn.astype(jnp.int32), toks)
            if counts is None:
                return toks
            return jnp.stack([toks, counts.astype(jnp.int32)])

        # the decode updates the cache in place: donated, the pools are
        # not held twice across the call
        self._decode = jax.jit(serve_decode, donate_argnums=1)
        self._prefill = jax.jit(serve_prefill)
        self._prefill_tail = jax.jit(serve_prefill_tail)
        # block writes update a pool in place: donated, no write holds
        # a second copy of a pool (a latent pool is 5.4 GB of 16)
        self._cow_kernel = jax.jit(serve_cow, donate_argnums=0)
        self._gather_blocks = jax.jit(serve_gather_blocks)
        self._scatter_blocks = jax.jit(serve_scatter_blocks,
                                       donate_argnums=0)
        self._sample_rows = jax.jit(serve_sample, static_argnames="sampled")

    # ------------------------------------------------------------------
    @property
    def decode_path(self) -> str:
        """The decode implementation ticks actually run through.

        ``"shard_map_flash_paged_2d"`` when the paged pool is 2-D
        sharded (block dim over data×model, batch partitioned across
        data); ``"shard_map_flash"`` when the 1-D sharded path really
        executes; ``"flash"`` when the internal single-shard combine
        takes over — model axis of size 1, or the sharded dim not
        divisible (``max_len`` for a dense cache, ``n_blocks`` for a
        paged pool); ``"pallas_paged"`` when the Pallas kernel reads a
        paged per-head K/V pool in place; ``"xla"`` when no mesh was
        provided.
        """
        impl = self.cfg.decode_impl
        if impl == "xla":
            return impl
        if impl == "pallas_paged":
            # lm.decode_step's own guard: a paged per-head K/V pool
            return impl if self.kv_residency == "paged" \
                and not self.arch.is_mla else "xla"
        if self.cfg.mesh is None:
            return "xla"               # lm.decode_step's own guard
        if impl == "shard_map_flash":
            from repro.dist.flash_decode import (pool_sharding_kind,
                                                 uses_seq_sharding)
            if self.kv_residency == "paged":
                kind = pool_sharding_kind(
                    self.cfg.mesh, self.n_blocks, self.max_batch,
                    self.cfg.data_axes, self.cfg.model_axis)
                if kind == "2d":
                    return "shard_map_flash_paged_2d"
                if kind == "none":
                    return "flash"     # flash_decode's single-shard path
            elif not uses_seq_sharding(self.cfg.mesh, self.max_len,
                                       self.cfg.model_axis):
                return "flash"
        return impl

    @property
    def combine_topology(self) -> str:
        """The model-axis softmax-combine topology decode ticks run —
        the same :func:`repro.dist.flash_decode.combine_topology`
        predicate the kernels dispatch on, with the engine's RunCfg
        override (a plan-recorded or caller-pinned topology) applied.
        Paths with no cross-shard combine (xla / single-shard flash)
        report "flat"."""
        if self.decode_path not in ("shard_map_flash",
                                    "shard_map_flash_paged_2d"):
            return "flat"
        from repro.dist.flash_decode import combine_topology
        return combine_topology(self.cfg.mesh,
                                model_axis=self.cfg.model_axis,
                                override=self.cfg.combine_topology)

    # ---------------- disaggregated prefill ---------------------------
    @property
    def prefill_mode(self) -> str:
        """Effective prefill mode: ``"disagg"`` while a fleet serves,
        ``"degraded"`` after the fleet exhausted its restart budget
        (prefill is back in-process), ``"inline"`` otherwise."""
        if self.degraded is not None:
            return "degraded"
        return self.kv_prefill_mode

    def attach_fleet(self, fleet) -> None:
        """Switch prefill to disaggregated mode through ``fleet`` (a
        :class:`repro.serve.disagg.PrefillFleet`).  Typed rejections:
        chunked block-native prefill needs the paged pool to scatter
        into and pure-attention KV to chunk (an SSM path's state is
        sequential across the whole prompt)."""
        _refuse_disagg_on_tpu()
        if self.kv_residency != "paged":
            raise ValueError(
                "disaggregated prefill streams pool-block-shaped KV "
                "chunks — a dense-residency engine has no block pool "
                "to scatter them into")
        if not self.arch.has_attention or self.arch.has_ssm:
            raise ValueError(
                f"disaggregated prefill needs pure-attention KV; "
                f"{self.arch.name} carries SSM state that is sequential "
                "across the whole prompt")
        self._fleet = fleet
        self.kv_prefill_mode = "disagg"

    def shutdown(self) -> None:
        """Stop the prefill fleet (if any).  Idempotent."""
        if self._fleet is not None:
            self._fleet.shutdown()

    @classmethod
    def from_plan(cls, plan, params, *, arch: Optional[ArchConfig] = None,
                  mesh=None, max_batch: Optional[int] = None,
                  max_len: Optional[int] = None, seed: int = 0,
                  kv_admission: Optional[str] = None,
                  kv_prefix_reuse: Optional[str] = None,
                  kv_host_blocks: Optional[int] = None,
                  kv_prefetch: Optional[str] = None,
                  combine_topology: Optional[str] = None,
                  preemption: Optional[PreemptionPolicy] = None,
                  kv_prefill_mode: Optional[str] = None,
                  disagg_workers: int = 0,
                  disagg_opts: Optional[Dict[str, Any]] = None,
                  fleet=None) -> "ServeEngine":
        """Build an engine from the frozen plan artifact.

        The plan supplies everything the kwargs constructor asks for:
        the RunCfg (flash-attention tiles, padded head counts, decode
        implementation; attention runs through XLA — the plan's
        ``use_pallas`` does not reach the model path), the KV-cache sizing
        (padded kv/ssm heads), the admission mode the cost model chose
        (``kv_admission`` — grow-on-demand grants when the pool is the
        reclamation bet, worst-case reservation when it covers every
        slot), and the batching limits (the workload dims carried in
        the artifact).  ``arch`` overrides the registry lookup for
        reduced/custom configs whose name shadows a registered one;
        ``max_batch``/``max_len`` override the plan limits (e.g. a
        single-host deployment of a decode_32k plan); ``kv_admission``
        overrides the plan's admission mode (an ops escape hatch —
        e.g. forcing ``reserve`` while diagnosing preemption churn).

        With a ``mesh`` the engine's params and KV cache are placed per
        the plan's axis rules and a ``shard_map_flash`` decode decision
        is honored end-to-end.  Without one the engine is
        single-process, so a plan that chose the seq-sharded decode
        falls back to the XLA decode path (the sharding decision needs
        a real mesh).

        Workload-dims compatibility is validated instead of silently
        sizing the cache from stale dims: a non-decode plan has no
        serving dims at all (both overrides are then required), and
        overrides *larger* than the dims the plan was specialized for
        are rejected — the pass sized the KV memory (and, for paged
        residency, the block pool) from those dims, so a bigger runtime
        cache needs a respecialized plan, not a quiet under-allocation.
        """
        from repro.core.passes.lowering import build_run_cfg
        arch = arch if arch is not None else get_arch(plan.arch)
        if plan.shape_kind != "decode":
            if max_batch is None or max_len is None:
                raise ValueError(
                    f"plan {plan.content_hash()[:12]} was specialized for "
                    f"shape_kind={plan.shape_kind!r}, not a decode workload; "
                    f"its dims (seq_len={plan.seq_len}, "
                    f"global_batch={plan.global_batch}) cannot size a "
                    "serving cache — pass max_batch= and max_len= "
                    "explicitly, or specialize a decode shape")
        else:
            if max_len is not None and plan.seq_len and max_len > plan.seq_len:
                raise ValueError(
                    f"max_len={max_len} exceeds the seq_len={plan.seq_len} "
                    f"this plan was specialized for — the pass sized the KV "
                    "memory from that dim; respecialize with the larger "
                    "decode shape instead of overriding past it")
            if max_batch is not None and plan.global_batch \
                    and max_batch > plan.global_batch:
                raise ValueError(
                    f"max_batch={max_batch} exceeds the global_batch="
                    f"{plan.global_batch} this plan was specialized for — "
                    "respecialize with the larger decode shape instead of "
                    "overriding past it")
        cfg = build_run_cfg(plan, arch, mesh)
        if mesh is None and cfg.decode_impl == "shard_map_flash":
            cfg = dataclasses.replace(cfg, decode_impl="xla")
        if cfg.decode_impl == "pallas_paged" \
                and jax.default_backend() != "tpu":
            # the Pallas kernel compiles for a TPU only; elsewhere the
            # XLA gather (its oracle) decodes
            cfg = dataclasses.replace(cfg, decode_impl="xla")
        if combine_topology is not None:
            # ops escape hatch, same shape as kv_admission: pin the
            # softmax-combine wire pattern over the plan's record
            cfg = dataclasses.replace(cfg, combine_topology=combine_topology)
        if max_batch is None:
            max_batch = (plan.global_batch
                         if plan.shape_kind == "decode" and plan.global_batch
                         else 8)
        if max_len is None:
            max_len = plan.seq_len or 512
        eng = cls(arch, params, cfg, max_batch=max_batch, max_len=max_len,
                  ssm_heads=cfg.ssm_heads_padded, kv_heads=cfg.kv_heads_padded,
                  seed=seed,
                  kv_residency=str(plan.estimates.get("kv_residency",
                                                      "dense")),
                  kv_block_len=int(plan.estimates.get("kv_block_len", 0)),
                  kv_n_blocks=int(plan.estimates.get("kv_n_blocks", 0)),
                  kv_admission=(kv_admission if kv_admission is not None
                                else str(plan.estimates.get("kv_admission",
                                                            "reserve"))),
                  kv_prefix_reuse=(
                      kv_prefix_reuse if kv_prefix_reuse is not None
                      else str(plan.estimates.get("kv_prefix_reuse", "on"))),
                  kv_host_blocks=(
                      kv_host_blocks if kv_host_blocks is not None
                      else int(plan.estimates.get("kv_host_blocks", 0))),
                  kv_prefetch=(
                      kv_prefetch if kv_prefetch is not None
                      else str(plan.estimates.get("kv_prefetch", "on"))),
                  preemption=preemption)
        eng.plan = plan
        if mesh is not None:
            eng._place_on_mesh(mesh)
        # disaggregated prefill: honor the pass's kv_prefill_mode
        # decision (or the override), spawning a supervised worker
        # fleet when the caller asked for workers.  disagg needs paged
        # residency and a pure-attention arch; anything else — and a
        # zero worker count — quietly keeps the inline path, exactly
        # like the pass's own inline fallback.
        pmode = (kv_prefill_mode if kv_prefill_mode is not None
                 else str(plan.estimates.get("kv_prefill_mode", "inline")))
        if pmode == "disagg":
            if fleet is None and disagg_workers > 0 \
                    and eng.kv_residency == "paged" \
                    and arch.has_attention and not arch.has_ssm:
                from repro.serve.disagg import PrefillFleet
                _refuse_disagg_on_tpu()        # before spawning workers
                fleet = PrefillFleet(
                    plan, arch, params, disagg_workers,
                    block_len=eng.block_len,
                    kv_heads=cfg.kv_heads_padded,
                    **(disagg_opts or {}))
            if fleet is not None:
                eng.attach_fleet(fleet)
        return eng

    def _place_on_mesh(self, mesh) -> None:
        """Shard params + session cache per the plan's axis rules."""
        from jax.sharding import NamedSharding
        from repro.core.passes.lowering import param_pspecs
        from repro.dist.sharding import cache_pspecs, mesh_sizes

        sizes = mesh_sizes(mesh)
        # resolve against the arrays actually handed to us — their shapes
        # may differ from the IR (reduced configs, caller-side padding)
        pspecs = param_pspecs(self.plan, self.arch, sizes,
                              shapes=self.params)
        self.params = jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
            self.params, pspecs)
        cshapes = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                   for k, v in self.cache.items()}
        cpspecs = cache_pspecs(self.plan, self.arch, cshapes, sizes)
        shardings = {k: NamedSharding(mesh, s) for k, s in cpspecs.items()}
        self.cache = {k: jax.device_put(v, shardings[k])
                      for k, v in self.cache.items()}
        self._pos_sharding = shardings["pos"]

    # ------------------------------------------------------------------
    def submit(self, prompt: np.ndarray, max_new_tokens: int = 16,
               temperature: float = 0.0,
               deadline_s: Optional[float] = None) -> int:
        """Queue a request.  ``deadline_s`` (relative seconds) sets a
        per-request deadline: still-pending requests past it are shed
        (``Request.error``) instead of served late, and deadline'd
        requests are spared by victim selection while any deadline-free
        victim exists.  Raises :class:`OverloadError` while the engine
        is past its preemption-rate threshold — reject at the door, not
        a queue that can only thrash."""
        prompt = np.asarray(prompt, np.int32)
        if len(prompt) + max_new_tokens > self.max_len:
            # past capacity the per-slot append clamps onto the last cache
            # row and silently corrupts the tail — refuse loudly instead
            raise ValueError(
                f"request needs {len(prompt)} prompt + {max_new_tokens} new "
                f"tokens > max_len={self.max_len} cache rows; raise max_len "
                "or lower max_new_tokens")
        if self.kv_residency == "paged":
            need = self._blocks_needed(len(prompt), max_new_tokens)
            sub = self.n_blocks // max(1, self.pool_groups)
            if need > sub:
                # a request draws all its blocks from ONE data shard's
                # sub-pool; even grow-on-demand admission would hold the
                # full budget simultaneously by its last tick — refuse
                # loudly, not a silent hang (or a preemption storm)
                raise ValueError(
                    f"request needs {need} blocks of {self.block_len} rows "
                    f"but each sub-pool holds only {sub} "
                    f"({self.n_blocks} blocks over {self.pool_groups} "
                    "sub-pool(s)); raise kv_n_blocks or lower "
                    "max_new_tokens")
        if self.overloaded():
            raise OverloadError(
                f"engine is shedding load: {self._recent_preemptions()} "
                f"preemption(s) in the last "
                f"{self.preemption.shed_window_ticks} ticks exceeds the "
                f"shed rate {self.preemption.shed_rate}/tick — back off "
                "and retry, or route to another replica")
        r = Request(self._rid, prompt, max_new_tokens, temperature,
                    t_submit=time.time())
        if deadline_s is not None:
            r.deadline = r.t_submit + deadline_s
        self._rid += 1
        self.pending.append(r)
        return r.rid

    def _blocks_needed(self, plen: int, max_new: int) -> int:
        """Blocks covering every cache row the request can ever touch
        (``plen`` prompt rows + one append per decode tick) — its
        lifetime *peak* holding under either admission mode.  A request
        the prefill sample already satisfies (``max_new <= 1``) finishes
        before any cache write and needs none."""
        if max_new <= 1:
            return 0
        return -(-(plen + max_new) // self.block_len)

    def _admission_blocks(self, r: Request) -> int:
        """Blocks admission must secure before prefilling ``r``:
        the full worst-case budget under ``reserve`` (mid-decode grants
        can then never fail), just the blocks covering the (re-)prefill
        rows under ``grant`` (the rest arrive one block boundary at a
        time)."""
        if self.kv_residency != "paged":
            return 0
        if r.max_new_tokens <= 1 and not r.out_tokens:
            return 0                   # satisfied by the prefill sample
        if self.kv_admission == "grant":
            return -(-len(r.feed_tokens) // self.block_len)
        return self._blocks_needed(len(r.prompt), r.max_new_tokens)

    def block_stats(self) -> Dict[str, int]:
        """Pool accounting (``free + in_use`` always equals ``total``
        per tier; dense engines report an empty 0-block pool).
        ``shared`` counts resident blocks with more than one holder;
        ``prefix_trie`` the blocks the prefix cache currently indexes;
        ``cached`` the engine-held cold blocks (trie-retained, either
        tier) the spill scheduler may reclaim at will."""
        st = self._alloc.stats()
        st["prefix_trie"] = (len(self._prefix)
                             if self._prefix is not None else 0)
        st["cached"] = len(self._cached)
        return st

    def pressure_stats(self) -> Dict[str, Any]:
        """Overload-degradation telemetry: how often the engine had to
        fall back down the grant → migrate → preempt → shed ladder —
        plus the prefix-sharing counters (blocks shared right now,
        tokens whose prefill was aliased away, CoW copies taken)."""
        return {"tick": self.tick,
                "preemptions": self.preemptions,
                "migrations": self.migrations,
                "grant_denials": self.grant_denials,
                "shed": len(self.shed),
                "parked": len(self.preempted),
                "overloaded": self.overloaded(),
                "shared_blocks": self._alloc.shared_blocks,
                "prefix_hits": (self._prefix.hits
                                if self._prefix is not None else 0),
                "prefix_hit_tokens": (self._prefix.hit_tokens
                                      if self._prefix is not None else 0),
                "prefix_trie": (len(self._prefix)
                                if self._prefix is not None else 0),
                "prefix_rides": self.prefix_rides,
                "cow_copies": self.cow_copies,
                "spills": self._alloc.spills,
                "promotes": self._alloc.promotes,
                "cached_blocks": len(self._cached),
                "prefill_mode": self.prefill_mode,
                "degraded": (self.degraded.to_json()
                             if self.degraded is not None else None),
                "disagg_dispatches": self.disagg_dispatches,
                "disagg_chunks": self.disagg_chunks,
                "disagg_resumes": self.disagg_resumes,
                "disagg_inflight": len(self._disagg),
                "sampled_batched": self.sampled_batched,
                "sampled_per_slot": self.sampled_per_slot,
                "decode_blocks_live": self.decode_blocks_live,
                "decode_blocks_table": self.decode_blocks_table,
                "moe_held_pairs": dict(self.moe_held_pairs)}

    def telemetry(self) -> Dict[str, Any]:
        """One JSON-serializable snapshot of everything the engine
        knows about itself — plan decisions, queue depths, prefill
        accounting, block-pool state, the degradation ladder, and (in
        disagg mode) the fleet's supervision counters.  This is THE
        observability surface: drivers dump it instead of growing their
        own ad-hoc per-mode prints, and tests pin that ``json.dumps``
        of it round-trips."""
        fleet = self._fleet.stats() if self._fleet is not None else None
        return {
            "tick": self.tick,
            "decode_path": self.decode_path,
            "combine_topology": self.combine_topology,
            "kv_residency": self.kv_residency,
            "kv_admission": self.kv_admission,
            "prefill_mode": self.prefill_mode,
            "requests": {
                "pending": len(self.pending),
                "active": len(self.active),
                "finished": len(self.finished),
                "shed": len(self.shed),
                "parked": len(self.preempted),
                "disagg_inflight": len(self._disagg),
            },
            "prefill": {
                "calls": self.prefill_calls,
                "batches": [int(b) for b in
                            list(self.prefill_batches)[-32:]],
                "rides": self.prefix_rides,
                "disagg": {
                    "dispatches": self.disagg_dispatches,
                    "chunks": self.disagg_chunks,
                    "resumes": self.disagg_resumes,
                    "fleet": fleet,
                },
            },
            "blocks": {k: int(v) for k, v in self.block_stats().items()},
            "pressure": self.pressure_stats(),
        }

    def _recent_preemptions(self) -> int:
        lo = self.tick - self.preemption.shed_window_ticks
        return sum(1 for t in self._preempt_ticks if t > lo)

    def overloaded(self) -> bool:
        """True while the recent preemption rate says new admissions
        would only thrash (the load-shedding trigger)."""
        return (self._recent_preemptions()
                > self.preemption.shed_rate
                * self.preemption.shed_window_ticks)

    def _slot_group(self, slot: int) -> int:
        """The data-shard sub-pool that hosts a slot: the batch dim is
        sharded contiguously across data, so slot ranges map 1:1 onto
        the pool's data-major sub-pools."""
        return slot * self.pool_groups // self.max_batch

    # ---------------- prefix matching at admission --------------------
    def _match_info(self, r: Request) -> Optional[Dict[str, Any]]:
        """Per-request match state for one admission pass: the feed's
        chain hashes plus a per-group memo of trie matches (matching is
        per sub-pool — the combine contract forbids foreign blocks)."""
        if self._prefix is None:
            return None
        return {"hashes": chain_hashes(r.feed_tokens, self.block_len),
                "matches": {}}

    def _match_for(self, r: Request, info: Optional[Dict[str, Any]],
                   group: int) -> List[int]:
        """Longest resident prefix of ``r``'s feed in ``group``'s trie,
        as block ids.  Capped one token short of the whole feed: the
        last feed token's compute must always run here (its logits seed
        a fresh request's sampling; its KV row is the one a resumed
        request's next tick appends)."""
        if info is None:
            return []
        got = info["matches"].get(group)
        if got is None:
            got = self._prefix.match(info["hashes"], group)
            cap = (len(r.feed_tokens) - 1) // self.block_len
            got = got[:cap]
            info["matches"][group] = got
        return got

    def _bucket_key(self, r: Request, matched: List[int]):
        """Admission bucket identity: ``(matched_tokens, tail_tokens)``.
        Compute-skip archs batch one jitted tail forward per bucket, so
        every member must skip the same row count; archs that cannot
        skip (SSM state) bucket by feed length alone."""
        flen = len(r.feed_tokens)
        if self._skip_prefix and matched:
            m = len(matched) * self.block_len
            return (m, flen - m)
        return (0, flen)

    def _can_ride(self, r: Request, matched: List[int]) -> bool:
        """True when admission can skip prefill *entirely*: a fresh
        request whose whole feed-but-last-token is aliased from the
        trie.  Its first decode tick feeds that last token and samples
        the first output — the decode-ride path (zero prefill calls;
        decode logits are bitwise the prefill logits for the same
        position, which the shared-prefix identity tests pin)."""
        if not (self._skip_prefix and matched and not r.out_tokens):
            return False
        if r.max_new_tokens <= 1:
            return False      # satisfied by the sample; never holds blocks
        return len(matched) * self.block_len == len(r.feed_tokens) - 1

    def _register_prefix(self, r: Request,
                         info: Optional[Dict[str, Any]],
                         group: int) -> None:
        """Index ``r``'s full feed blocks in its sub-pool's trie (first
        writer wins) and remember the hashes for migration re-keying."""
        if self._prefix is None or info is None or not r.blocks:
            return
        hashes = info["hashes"]
        r.prefix_hashes = list(hashes)
        self._prefix.insert(hashes, r.blocks[:len(hashes)], group)

    def _release_blocks(self, blocks: List[int]) -> None:
        """Drop one holder reference per block; prune trie entries for
        the blocks that actually left the pool (a freed id's next
        tenant writes unrelated rows).

        With tiering on, a block whose *last* holder is releasing but
        which the prefix trie still indexes is not freed — the engine
        keeps the reference and parks the id in its cold-block cache
        (``_cached``), a page-cache bet: the content costs nothing
        until pressure, and a future admission with the same prefix
        aliases it instead of re-prefilling.  The spill scheduler
        (:meth:`_spill_cold`) reclaims cached blocks on demand — spill
        to host first, drop outright only when the host tier is full
        too."""
        if self.kv_tiering and self._prefix is not None:
            kept = []
            for b in blocks:
                if self._alloc.refcount(b) == 1 \
                        and self._prefix.has_block(b) \
                        and b not in self._cached:
                    self._cached[b] = None
                else:
                    kept.append(b)
            blocks = kept
        if not blocks:
            return
        freed = self._alloc.release(blocks)
        if self._prefix is not None and freed:
            self._prefix.evict(freed)

    # ---------------- tier transitions + spill scheduler --------------
    def _spill_rows(self, pairs: List[Tuple[int, int]]) -> None:
        """Copy the rows of just-spilled blocks into the host pool (one
        batched device→host gather per cache pool).  The vacated HBM
        ids are already back on their free lists, but their rows stay
        intact until a next tenant writes — the copy races nothing."""
        with span("serve.spill", blocks=len(pairs)):
            old_ids = jnp.asarray(np.asarray([b for b, _ in pairs],
                                             np.int32))
            idx = np.asarray([h - self.n_blocks for _, h in pairs],
                             np.int64)
            for key in self._pools:
                self._host[key][:, idx] = np.asarray(
                    self._gather_blocks(self.cache[key], old_ids))

    def _promote_rows(self, pairs: List[Tuple[int, int]],
                      rows: Optional[Dict[str, Any]] = None) -> None:
        """Copy spilled rows back into the device pools at the pairs'
        new HBM ids — from the prefetcher's staged device arrays
        (``rows``, one per pool) when they landed, else a synchronous
        host→device transfer (the stall ``kv_prefetch="off"``
        benchmarks)."""
        with span("serve.promote", blocks=len(pairs)):
            idx = np.asarray([h - self.n_blocks for h, _ in pairs],
                             np.int64)
            new_ids = jnp.asarray(np.asarray([b for _, b in pairs],
                                             np.int32))
            if rows is None:
                rows = {key: jnp.asarray(self._host[key][:, idx])
                        for key in self._pools}
            for key in self._pools:
                self.cache[key] = self._scatter_blocks(self.cache[key],
                                                       new_ids, rows[key])

    def _promote_matched(self, matched: List[int],
                         group: int) -> List[int]:
        """Resolve a matched block list to decode-ready HBM ids — the
        hit-after-spill path.  Ids another request promoted earlier in
        this same admission pass are followed through ``_promo_map``
        (their host ids are already back on the host free list); any
        still-host-resident block is promoted into ``group`` now: rows
        copied back, trie and cold-cache entries re-keyed.  Placement
        already budgeted the draws (:meth:`_hbm_matched`)."""
        if not self.kv_tiering or not matched:
            return list(matched)
        out = [self._promo_map.get(b, b) for b in matched]
        host_ids = [b for b in out if self._alloc.tier_of(b) == "host"]
        if not host_ids:
            return out
        pairs = self._alloc.promote(host_ids, group)
        assert pairs is not None, "placement budgeted the promote draw"
        self._promote_rows(pairs)
        self._prefix.rekey(pairs, "hbm")
        for old, new in pairs:
            if old in self._cached:
                del self._cached[old]
                self._cached[new] = None
            self._promo_map[old] = new
        trans = dict(pairs)
        return [trans.get(b, b) for b in out]

    def _evict_cached_host(self, n: int) -> int:
        """Drop up to ``n`` oldest engine-cached *host*-tier blocks
        outright (free the ids, prune the trie) — the host pool's own
        reclamation, run when a spill or a park finds it full."""
        victims = [b for b in self._cached
                   if self._alloc.tier_of(b) == "host"][:n]
        for b in victims:
            del self._cached[b]
            freed = self._alloc.release([b])
            if self._prefix is not None and freed:
                self._prefix.evict(freed)
        return len(victims)

    def _spill_cold(self, group: int, need: int) -> int:
        """The reclaim rung *before* the grant → migrate → preempt →
        shed ladder: free up to ``need`` HBM blocks in ``group`` by
        moving the engine's oldest cached (cold, trie-retained) blocks
        to the host tier.  Cold-block selection is insertion order over
        ``_cached`` — exactly the blocks idle sessions, evicted trie
        tails, and fully-decoded prompts left behind, oldest first.
        When the host pool is full the oldest cached host block is
        evicted to make room; when there is no host room at all the
        cold block is dropped outright (it was a cache — the content
        is reconstructible by re-prefill).  Blocks an admission has
        since aliased (refcount > 1) are pinned: an active table points
        at them.  Returns the number of HBM blocks actually freed."""
        if not self.kv_tiering:
            return 0
        freed = 0
        while freed < need:
            cand = next((b for b in self._cached
                         if b < self.n_blocks
                         and self._alloc.group_of(b) == group
                         and self._alloc.refcount(b) == 1), None)
            if cand is None:
                break
            if self._alloc.host_free == 0:
                self._evict_cached_host(1)
            if self._alloc.host_free > 0:
                pairs = self._alloc.spill([cand])
                assert pairs is not None, "host headroom was just checked"
                self._spill_rows(pairs)
                self._prefix.rekey(pairs, "host")
                del self._cached[cand]
                self._cached[pairs[0][1]] = None
            else:
                del self._cached[cand]
                fr = self._alloc.release([cand])
                if self._prefix is not None and fr:
                    self._prefix.evict(fr)
            freed += 1
        return freed

    def spill_cached(self, group: Optional[int] = None) -> int:
        """Force-spill every unpinned cached HBM block to the host tier
        (test/ops hook: drives the hit-after-spill path without real
        pool pressure).  Returns the number of blocks spilled."""
        total = 0
        gs = range(self.pool_groups) if group is None else [group]
        for g in gs:
            n = sum(1 for b in self._cached
                    if b < self.n_blocks and self._alloc.group_of(b) == g
                    and self._alloc.refcount(b) == 1)
            total += self._spill_cold(g, n)
        return total

    def drop_block_cache(self) -> int:
        """Release every engine-cached cold block (both tiers) and
        prune their trie entries — the test/ops hook that restores the
        exact-leak-check identity (``free == total`` per tier once no
        requests are live).  Returns the number of blocks freed."""
        blocks = list(self._cached)
        self._cached.clear()
        freed = self._alloc.release(blocks) if blocks else []
        if self._prefix is not None and freed:
            self._prefix.evict(freed)
        return len(freed)

    def _hbm_matched(self, matched: List[int]) -> int:
        """Matched trie blocks that are already HBM-resident — only
        those reduce the admission draw.  A host-tier match still saves
        the prefill compute, but its promote consumes one free HBM
        block from the slot's sub-pool exactly like a fresh allocation
        would, so placement must budget for it."""
        if not self.kv_tiering:
            return len(matched)
        return sum(1 for b in matched if self._alloc.tier_of(b) == "hbm")

    def _place(self, r: Request, avail: List[int],
               free_by_group: Dict[int, int],
               info: Optional[Dict[str, Any]] = None) -> Optional[int]:
        """Reserve a free slot whose sub-pool can cover ``r``'s
        admission block need net of aliased blocks; mutates both
        accounting structures.  With match info and multiple sub-pools,
        slots are tried longest-match-first (admission prefers the
        sub-pool holding the longest resident prefix), FIFO otherwise.
        """
        need_full = self._admission_blocks(r)
        order = list(range(len(avail)))
        if info is not None and self.pool_groups > 1:
            order.sort(key=lambda i: (
                -len(self._match_for(r, info, self._slot_group(avail[i]))),
                avail[i]))
        for i in order:
            g = self._slot_group(avail[i])
            matched = self._match_for(r, info, g) if info is not None else []
            need = max(0, need_full - self._hbm_matched(matched))
            if need <= free_by_group[g]:
                free_by_group[g] -= need
                return avail.pop(i)
        return None

    def _place_bucket(self, r: Request, info: Optional[Dict[str, Any]],
                      key, avail: List[int],
                      free_by_group: Dict[int, int]) -> Optional[int]:
        """Like :meth:`_place`, but only into a slot whose sub-pool's
        match keeps ``r`` in the head request's admission bucket (same
        skipped-prefix length, same tail length)."""
        need_full = self._admission_blocks(r)
        for i, s in enumerate(avail):
            g = self._slot_group(s)
            matched = self._match_for(r, info, g) if info is not None else []
            if self._bucket_key(r, matched) != key:
                continue
            need = max(0, need_full - self._hbm_matched(matched))
            if need <= free_by_group[g]:
                free_by_group[g] -= need
                return avail.pop(i)
        return None

    # ---------------- disaggregated prefill paths ---------------------
    def _admit_disagg(self) -> None:
        """Head-of-line admission in disagg mode: fully-matched feeds
        still ride inline (zero prefill either way); everything else
        reserves a slot plus its FULL admission-block need and
        dispatches to the worker fleet.  Partial prefix matches are not
        aliased on this path — the worker recomputes the whole feed and
        the trie indexes the finished blocks at completion."""
        self._promo_map.clear()
        while self.pending and self.free_slots:
            head = self.pending[0]
            info = self._match_info(head)
            # alias-aware probe: decode-ride beats any dispatch
            avail = list(self.free_slots)
            fbg = {g: self._alloc.free_in(g)
                   for g in range(self.pool_groups)}
            s_alias = self._place(head, avail, fbg, info)
            if s_alias is not None and self._can_ride(
                    head,
                    self._match_for(head, info,
                                    self._slot_group(s_alias))):
                self.pending.pop(0)
                self.free_slots.remove(s_alias)
                self._admit_ride(head, s_alias, info)
                continue
            avail = list(self.free_slots)
            fbg = {g: self._alloc.free_in(g)
                   for g in range(self.pool_groups)}
            s0 = self._place(head, avail, fbg, None)
            if s0 is None and self.kv_tiering and self._cached:
                # tier rung: spill cold cached blocks, retry once
                need0 = self._admission_blocks(head)
                for g in range(self.pool_groups):
                    short = need0 - self._alloc.free_in(g)
                    if short > 0:
                        self._spill_cold(g, short)
                avail = list(self.free_slots)
                fbg = {g: self._alloc.free_in(g)
                       for g in range(self.pool_groups)}
                s0 = self._place(head, avail, fbg, None)
            if s0 is None:
                return             # pool exhausted: wait for frees
            if head.rid in self._inline_poison:
                # this rid's worker prefill raised (deterministically,
                # as far as we know): run it in-process instead
                self.pending.pop(0)
                self.free_slots.remove(s0)
                self._admit_group([head], [s0])
                continue
            if not self._dispatch_prefill(head, s0):
                return             # no live worker (respawn in flight)
            self.pending.pop(0)
            self.free_slots.remove(s0)

    def _dispatch_prefill(self, r: Request, slot: int,
                          start_block: int = 0,
                          flight: Optional[_DisaggFlight] = None) -> bool:
        """Ship ``r``'s feed to the fleet.  A fresh dispatch allocates
        the admission blocks first — they are the journal's scatter
        target; a re-dispatch (``flight`` set) keeps them and gathers
        the journaled blocks' rows back out of the pool as the worker's
        resume prefix (token-identical: the rows ARE the prefix KV a
        dense prefill would have computed)."""
        g = self._slot_group(slot)
        fresh = flight is None
        if fresh:
            blocks = self._alloc.allocate(self._admission_blocks(r), g)
            if blocks is None:
                return False       # placement said yes; lost the race
            r.blocks = blocks
        prefix = None
        if start_block:
            ids = jnp.asarray(np.asarray(r.blocks[:start_block], np.int32))
            m = start_block * self.block_len
            prefix = {}
            for key in self._pools:
                rows = np.asarray(self._gather_blocks(self.cache[key], ids))
                prefix[key] = rows.reshape(rows.shape[0], m, *rows.shape[3:])
        feed = r.feed_tokens
        ok = self._fleet.dispatch(
            r.rid, feed[start_block * self.block_len:], prefix=prefix)
        if not ok:
            if fresh and r.blocks:
                self._release_blocks(r.blocks)
                r.blocks = []
            return False
        if fresh:
            r.slot = int(slot)
            self._disagg[r.rid] = _DisaggFlight(
                request=r, slot=slot, group=g,
                nb_feed=-(-len(feed) // self.block_len))
        elif start_block:
            self.disagg_resumes += 1
        self.disagg_dispatches += 1
        return True

    def _on_chunk(self, fl: _DisaggFlight, idx: int,
                  rows: Dict[str, Any]) -> None:
        """Scatter one streamed pool-block-shaped slab of each cache
        pool into the paged pools (the tier-spill mover run in reverse)
        and advance
        the journal.  Chunks re-sent after a re-dispatch overwrite
        bit-identical rows — idempotent by the chunked-prefill
        contract.  Requests with no blocks (satisfied by the prefill
        sample) only need the logits, so their chunks drop."""
        r = fl.request
        if not r.blocks or idx >= len(r.blocks) or idx > fl.acked:
            return
        bid = jnp.asarray(np.asarray([r.blocks[idx]], np.int32))
        t = 0
        for key in self._pools:
            part = np.asarray(rows[key])
            t = part.shape[1]
            if t < self.block_len:
                # partial tail block: pad to block shape (slot_len masks
                # the zero rows, exactly like the inline scatter's clamp)
                full = np.zeros((part.shape[0], self.block_len,
                                 *part.shape[2:]), part.dtype)
                full[:, :t] = part
                part = full
            self.cache[key] = self._scatter_blocks(
                self.cache[key], bid, jnp.asarray(part)[:, None])
        self.disagg_chunks += 1
        if t == self.block_len and idx == fl.acked:
            fl.acked = idx + 1

    def _complete_prefill(self, fl: _DisaggFlight, logits) -> None:
        """A worker finished a prompt: install the block-table row,
        activate the slot, and (for fresh requests) sample the first
        token from the streamed logits — bitwise the logits the inline
        prefill would have produced."""
        r, slot = fl.request, fl.slot
        del self._disagg[r.rid]
        if r.rid in self._redispatch:
            self._redispatch.remove(r.rid)
        self.prefill_calls += 1
        self.prefill_batches.append(1)
        if r.blocks:
            rows = np.full((int(self.cache["block_tbl"].shape[1]),), -1,
                           np.int32)
            rows[:len(r.blocks)] = r.blocks
            self.cache["block_tbl"] = \
                self.cache["block_tbl"].at[slot].set(jnp.asarray(rows))
        if not r.out_tokens:
            self.sampled_per_slot += 1
            tok = self._sample(r, jnp.asarray(np.asarray(logits)),
                               self._next_key())
            r.out_tokens.append(int(tok))
            r.t_first = time.time()
            if len(r.out_tokens) >= r.max_new_tokens:
                r.done = True
                r.t_done = r.t_first
                self.finished.append(r)
                self._release_slot(slot, r)
                return
        # a resumed re-prefill keeps its retained tokens: the sample
        # these logits would re-derive is already on the host
        self.slot_len[slot] = len(r.feed_tokens)
        r.slot = int(slot)
        self.active[slot] = r
        if self._prefix is not None and r.blocks:
            hashes = chain_hashes(r.feed_tokens, self.block_len)
            r.prefix_hashes = list(hashes)
            self._prefix.insert(hashes, r.blocks[:len(hashes)], fl.group)
            self._prefix.misses += 1

    def _abort_flight(self, fl: _DisaggFlight) -> None:
        """Take a flight out of service: journal dropped, blocks
        released, slot returned.  The fleet-side cancel makes any late
        chunks from a still-running worker drop on the floor."""
        r = fl.request
        self._disagg.pop(r.rid, None)
        if r.rid in self._redispatch:
            self._redispatch.remove(r.rid)
        if self._fleet is not None:
            self._fleet.cancel(r.rid)
        if r.blocks:
            self._release_blocks(r.blocks)
            r.blocks = []
        self.free_slots.append(fl.slot)
        self.slot_len[fl.slot] = 0
        fl.acked = 0

    def _on_worker_error(self, rid: int, err: str) -> None:
        """A worker's prefill *raised* for this request (poison input,
        not a process death): re-dispatching would loop forever, so the
        flight aborts and the request re-queues marked inline-only."""
        fl = self._disagg.get(rid)
        if fl is None:
            return
        r = fl.request
        self._abort_flight(fl)
        self._inline_poison.add(rid)
        self.pending.insert(0, r)

    def _shed_expired_flights(self) -> None:
        """Deadline shedding composes with disagg: a request whose
        deadline passes mid-prefill sheds exactly like a pending one —
        blocks released, same ``Request.error`` surface."""
        if not self._disagg:
            return
        if not any(fl.request.deadline is not None
                   for fl in self._disagg.values()):
            return
        now = time.time()
        for fl in list(self._disagg.values()):
            r = fl.request
            if r.deadline is not None and now > r.deadline:
                self._abort_flight(fl)
                self._shed(r, f"deadline missed during disagg prefill "
                              f"(tick {self.tick})")

    def _enter_degraded(self) -> None:
        """Every fleet slot retired past its restart budget: flip to
        in-process prefill under a typed ``DegradedMode`` and re-queue
        the orphaned flights at the front of the pending queue, oldest
        first.  Token-identical under greedy sampling — the inline
        re-prefill rebuilds exactly the KV the workers would have
        streamed."""
        if self.degraded is not None:
            return
        self.degraded = dataclasses.replace(self._fleet.degraded,
                                            at_tick=self.tick)
        for rid in sorted(self._disagg.keys(), reverse=True):
            fl = self._disagg[rid]
            self._abort_flight(fl)
            self.pending.insert(0, fl.request)
        self._redispatch = []
        self._fleet.shutdown()

    def _poll_disagg(self) -> None:
        """Drain fleet events: scatter arrived chunks, complete
        finished prefills, queue re-dispatches for rids a worker death
        orphaned, degrade when the whole fleet has retired — then retry
        queued re-dispatches (resuming at the last acked block
        boundary, never past the final block so the worker always has
        at least one tail token to derive the logits from)."""
        if self._fleet is None or self.degraded is not None:
            return
        for ev in self._fleet.poll():
            kind = ev[0]
            if kind == "chunk":
                fl = self._disagg.get(ev[1])
                if fl is not None:
                    self._on_chunk(fl, ev[2], ev[3])
            elif kind == "done":
                fl = self._disagg.get(ev[1])
                if fl is not None:
                    self._complete_prefill(fl, ev[2])
            elif kind == "dead":
                if ev[1] in self._disagg \
                        and ev[1] not in self._redispatch:
                    self._redispatch.append(ev[1])
            elif kind == "error":
                self._on_worker_error(ev[1], ev[2])
        if self._fleet.degraded is not None:
            self._enter_degraded()
            return
        still: List[int] = []
        for rid in self._redispatch:
            fl = self._disagg.get(rid)
            if fl is None:
                continue
            start = min(fl.acked, fl.nb_feed - 1) if fl.request.blocks \
                else 0
            if not self._dispatch_prefill(fl.request, fl.slot,
                                          start_block=start, flight=fl):
                still.append(rid)
        self._redispatch = still

    def _admit(self) -> None:
        """Bucketed batched admission: all pending prompts sharing the
        head-of-line's bucket — feed length, plus skipped-prefix length
        when prefix reuse matches resident blocks — that fit a (slot,
        sub-pool) pair are prefilled in ONE jitted call (tail-only when
        a prefix is aliased).  A request whose whole feed-but-last is
        resident skips prefill entirely and goes straight to decode.
        A request takes its admission blocks from the sub-pool of the
        data shard hosting its slot (2-D pool sharding; one global pool
        when ``pool_groups == 1``).  When no pair can cover the head
        request, admission waits for a finisher — head-of-line
        blocking, so exhaustion delays rather than starves (and
        ``run_until_idle`` raises on true deadlock).

        In disagg mode admission routes through
        :meth:`_admit_disagg` instead (dispatch to the worker fleet;
        rides still inline).
        """
        if self._fleet is not None and self.prefill_mode == "disagg":
            self._admit_disagg()
            return
        self._promo_map.clear()        # promoted-id map is per admission
        while self.pending and self.free_slots:
            head = self.pending[0]
            info0 = self._match_info(head)
            avail = list(self.free_slots)
            free_by_group = {g: self._alloc.free_in(g)
                             for g in range(self.pool_groups)}
            s0 = self._place(head, avail, free_by_group, info0)
            if s0 is None:
                if not (self.kv_tiering and self._cached):
                    return             # pool exhausted: wait for frees
                # tier rung: spill cold cached blocks to host until some
                # sub-pool can cover the head, then retry the placement
                # once (the match memo is stale after a rekey)
                need0 = self._admission_blocks(head)
                for g in range(self.pool_groups):
                    short = need0 - self._alloc.free_in(g)
                    if short > 0:
                        self._spill_cold(g, short)
                info0 = self._match_info(head)
                avail = list(self.free_slots)
                free_by_group = {g: self._alloc.free_in(g)
                                 for g in range(self.pool_groups)}
                s0 = self._place(head, avail, free_by_group, info0)
                if s0 is None:
                    return             # truly exhausted: wait for frees
            m0 = self._match_for(head, info0, self._slot_group(s0))
            if self._can_ride(head, m0):
                self.pending.pop(0)
                self.free_slots.remove(s0)
                self._admit_ride(head, s0, info0)
                continue
            key0 = self._bucket_key(head, m0)
            group: List[Request] = [head]
            slots: List[int] = [s0]
            matches: List[List[int]] = [m0]
            infos: List[Optional[Dict[str, Any]]] = [info0]
            rest: List[Request] = []
            for r in self.pending[1:]:
                s = None
                info = None
                if len(r.feed_tokens) == len(head.feed_tokens):
                    info = self._match_info(r)
                    s = self._place_bucket(r, info, key0, avail,
                                           free_by_group)
                if s is None:
                    rest.append(r)
                else:
                    group.append(r)
                    slots.append(s)
                    matches.append(
                        self._match_for(r, info, self._slot_group(s)))
                    infos.append(info)
            self.pending = rest
            for s in slots:
                self.free_slots.remove(s)
            if self.kv_tiering:
                # hit-after-spill: matched lists may name host-tier (or
                # already-promoted) blocks — resolve them to HBM ids
                # before any gather or alias touches the device pool
                matches = [self._promote_matched(m, self._slot_group(s))
                           for m, s in zip(matches, slots)]
            self._admit_group(group, slots, matches, infos, key0)

    def _admit_ride(self, r: Request, slot: int,
                    info: Dict[str, Any]) -> None:
        """Zero-prefill admission: alias the matched blocks (refcount
        bump), grant the fresh ones the budget calls for, install the
        table row, and hand the request straight to decode — its first
        tick feeds the last prompt token at position ``matched_tokens``
        and samples the first output."""
        g = self._slot_group(slot)
        matched = self._promote_matched(self._match_for(r, info, g), g)
        need = self._admission_blocks(r)
        self._alloc.retain(matched)
        fresh = self._alloc.allocate(need - len(matched), g)
        assert fresh is not None, "placement checked the free count"
        r.blocks = list(matched) + fresh
        rows = np.full((int(self.cache["block_tbl"].shape[1]),), -1,
                       np.int32)
        rows[:len(r.blocks)] = r.blocks
        self.cache["block_tbl"] = \
            self.cache["block_tbl"].at[slot].set(jnp.asarray(rows))
        r.slot = int(slot)
        m_tok = len(matched) * self.block_len
        self.slot_len[slot] = m_tok
        self.active[slot] = r
        self._register_prefix(r, info, g)
        self._prefix.hits += 1
        self._prefix.hit_tokens += m_tok
        self.prefix_rides += 1

    def _admit_group(self, group: List[Request], slots: List[int],
                     matches: Optional[List[List[int]]] = None,
                     infos: Optional[List[Optional[Dict[str, Any]]]] = None,
                     bucket=None) -> None:
        """One jitted prefill for a bucket of requests, each with a
        pre-reserved slot (its sub-pool is the one the request's blocks
        will come from).  A resumed (previously preempted) request's
        feed is prompt+generated-so-far: the prefill rebuilds its KV
        rows and its sample is discarded — the host already holds the
        token it would re-derive.

        With a nonzero skipped-prefix bucket (compute-skip archs whose
        members all matched the same number of resident blocks), the
        matched rows are *gathered from the pool* and only the tail
        runs through :func:`repro.models.lm.prefill_tail`; the matched
        blocks are aliased, not rewritten.

        The batch dim is padded to the next power of two (dummy rows
        repeat the first prompt and are discarded), so each prompt
        length compiles at most ``log2(max_batch)`` prefill programs
        instead of one per arrival-group size."""
        if matches is None:
            matches = [[] for _ in group]
        if infos is None:
            infos = [None] * len(group)
        m_tok = bucket[0] if bucket else 0
        with span("serve.prefill", rids=[r.rid for r in group],
                  batch=len(group),
                  tokens=len(group[0].feed_tokens) - m_tok,
                  aliased=m_tok):
            toks = np.stack([r.feed_tokens[m_tok:] for r in group])
            padded = 1
            while padded < len(group):
                padded *= 2
            # never a batch no engine fills
            padded = min(padded, self.max_batch)
            if padded > len(group):
                toks = np.concatenate(
                    [toks, np.repeat(toks[:1], padded - len(group), axis=0)])
            cacheg = None
            if m_tok:
                # gather the aliased prefix rows (resident pool blocks) as
                # the tail forward's K/V prefix; dummy batch rows reuse the
                # first member's blocks (discarded, and read-only anyway)
                nbm = m_tok // self.block_len
                blk = np.asarray(matches, np.int32)            # (Bs, nbm)
                if padded > len(group):
                    blk = np.concatenate(
                        [blk, np.repeat(blk[:1], padded - len(group), axis=0)])
                bid = jnp.asarray(blk.reshape(-1))
                pre = {}
                for key in self._pools:
                    rows = self._gather_blocks(self.cache[key], bid)
                    pre[key] = rows.reshape(rows.shape[0], padded, m_tok,
                                            *rows.shape[3:])
                logits, tail, stats = self._prefill_tail(
                    self.params, {"tokens": jnp.asarray(toks)}, pre)
            else:
                logits, cacheg, stats = self._prefill(
                    self.params, {"tokens": jnp.asarray(toks)})
            self.prefill_calls += 1
            self.prefill_batches.append(len(group))
            live: List[Request] = []
            idxs: List[int] = []
            live_slots: List[int] = []
            # the first sample also waits for the prefill dispatched above
            batched = self._samples_batched()
            with span("serve.sample", slots=len(group),
                      rids=[r.rid for r in group], batched=batched):
                keys = jax.random.split(self._next_key(), len(group))
                # a resumed request's row, like a dummy row, is not drawn
                toks = self._draw(logits, keys, {
                    i: r for i, r in enumerate(group) if not r.out_tokens},
                    batched, stats, "prefill", range(len(group)))
                for i, r in enumerate(group):
                    if r.out_tokens:
                        # resumed after preemption: keep the retained tokens,
                        # keep decoding from where the eviction cut in
                        live.append(r)
                        idxs.append(i)
                        live_slots.append(slots[i])
                        continue
                    r.out_tokens.append(toks[i])
                    r.t_first = time.time()
                    if len(r.out_tokens) >= r.max_new_tokens:
                        # the prefill sample already met the budget: finish
                        # now — no decode tick to over-generate on, no cache
                        # copy, no blocks ever allocated, and the reserved
                        # slot goes back
                        r.done = True
                        r.t_done = r.t_first
                        self.finished.append(r)
                        self.free_slots.append(slots[i])
                    else:
                        live.append(r)
                        idxs.append(i)
                        live_slots.append(slots[i])
            if not live:
                return
            plen = len(live[0].feed_tokens)
            slots = np.asarray(live_slots, np.int32)
            gidx = np.asarray(idxs, np.int32)
            live_matches = [matches[i] for i in idxs]
            if self.arch.has_attention:
                if self.kv_residency == "paged":
                    if m_tok:
                        self._scatter_tail(live, slots, gidx, tail,
                                           plen, m_tok, live_matches)
                    else:
                        self._scatter_paged_prefill(live, slots, gidx, cacheg,
                                                    plen, live_matches)
                else:
                    for key in self._pools:
                        self.cache[key] = self.cache[key].at[:, slots].set(
                            cacheg[key][:, gidx])
            for key in ("ssm", "conv"):
                if key in self.cache:
                    self.cache[key] = self.cache[key].at[:, slots].set(
                        cacheg[key][:, gidx])
            for slot, r in zip(slots, live):
                r.slot = int(slot)
                self.slot_len[slot] = plen
                self.active[int(slot)] = r
            for i, r in enumerate(live):
                g = self._slot_group(int(slots[i]))
                self._register_prefix(r, infos[idxs[i]], g)
                if self._prefix is not None:
                    mt = live_matches[i]
                    if mt:
                        self._prefix.hits += 1
                        self._prefix.hit_tokens += len(mt) * self.block_len
                    else:
                        self._prefix.misses += 1

    def _scatter_paged_prefill(self, live: List[Request], slots: np.ndarray,
                               gidx: np.ndarray, cacheg, plen: int,
                               matches: Optional[List[List[int]]] = None
                               ) -> None:
        """Move a bucket's prefilled cache rows into their pool blocks.

        Each survivor gets its admission block budget now — matched
        blocks aliased with a refcount bump, the rest freshly allocated
        from *its slot's sub-pool* (admission reserved them, so the
        draw cannot fail).  Only the *unmatched* feed columns are
        scattered (a matched block already holds exactly those rows —
        writing them again would race a sharer's CoW), in one
        gather/scatter per cache tensor; then the block-table rows are
        installed (-1 padding past the allocation).  This is the path
        hybrid (SSM-carrying) archs take on a prefix hit: full-feed
        prefill for the state, aliased capacity for the matched KV.
        """
        bl = self.block_len
        nbp = -(-plen // bl)               # blocks holding prefilled rows
        nb_cols = self.cache["block_tbl"].shape[1]
        rows = np.full((len(live), nb_cols), -1, np.int32)
        ent_req: List[int] = []            # prefill batch row per block
        ent_col: List[int] = []            # feed block column per block
        ent_blk: List[int] = []            # destination pool block
        for i, r in enumerate(live):
            matched = list(matches[i]) if matches else []
            need = self._admission_blocks(r)
            assert need >= nbp >= len(matched), (need, nbp, len(matched))
            if matched:
                self._alloc.retain(matched)
            fresh = self._alloc.allocate(need - len(matched),
                                         self._slot_group(int(slots[i])))
            assert fresh is not None, "admission reserved these blocks"
            r.blocks = matched + fresh
            rows[i, :need] = r.blocks
            for c in range(len(matched), nbp):
                ent_req.append(int(gidx[i]))
                ent_col.append(c)
                ent_blk.append(r.blocks[c])
        if ent_blk:
            S = cacheg[self._pools[0]].shape[2]
            req = jnp.asarray(np.asarray(ent_req, np.int32)[:, None])
            ridx = np.asarray(ent_col, np.int32)[:, None] * bl \
                + np.arange(bl, dtype=np.int32)[None, :]
            # rows past an unaligned max_len clamp onto the last cache
            # row: garbage, but masked (pos >= cache_len) until a decode
            # append overwrites them
            ridx = jnp.asarray(np.minimum(ridx, S - 1))
            blk_ids = jnp.asarray(np.asarray(ent_blk, np.int32))
            for key in self._pools:
                upd = cacheg[key][:, req, ridx]        # (L, E, bl, *row)
                self.cache[key] = self._scatter_blocks(self.cache[key],
                                                       blk_ids, upd)
        self.cache["block_tbl"] = \
            self.cache["block_tbl"].at[slots].set(jnp.asarray(rows))

    def _scatter_tail(self, live: List[Request], slots: np.ndarray,
                      gidx: np.ndarray, tail: Dict[str, Any], plen: int,
                      m_tok: int, matches: List[List[int]]) -> None:
        """Install aliased-prefix block tables and scatter the
        tail-only prefill's rows of each pool into freshly granted
        blocks (the
        compute-skip counterpart of :meth:`_scatter_paged_prefill`:
        the first ``m_tok`` rows were never recomputed — their blocks
        are aliased as-is)."""
        bl = self.block_len
        nbm = m_tok // bl
        nbp = -(-plen // bl)
        nb_cols = self.cache["block_tbl"].shape[1]
        rows = np.full((len(live), nb_cols), -1, np.int32)
        tail_blocks: List[int] = []
        for i, r in enumerate(live):
            matched = list(matches[i])
            assert len(matched) == nbm, (len(matched), nbm)
            need = self._admission_blocks(r)
            assert need >= nbp > nbm, (need, nbp, nbm)
            self._alloc.retain(matched)
            fresh = self._alloc.allocate(need - nbm,
                                         self._slot_group(int(slots[i])))
            assert fresh is not None, "admission reserved these blocks"
            r.blocks = matched + fresh
            rows[i, :need] = r.blocks
            tail_blocks.extend(r.blocks[nbm:nbp])
        ntb = nbp - nbm
        blk_ids = jnp.asarray(np.asarray(tail_blocks, np.int32))
        T = plen - m_tok
        for key in self._pools:
            upd = tail[key][:, gidx]                   # (L, Bs, T, *row)
            L = upd.shape[0]
            if T < ntb * bl:
                upd = jnp.pad(upd, ((0, 0), (0, 0), (0, ntb * bl - T))
                              + ((0, 0),) * (upd.ndim - 3))
            upd = upd.reshape(L, len(live) * ntb, bl, *upd.shape[3:])
            self.cache[key] = self._scatter_blocks(self.cache[key],
                                                   blk_ids, upd)
        self.cache["block_tbl"] = \
            self.cache["block_tbl"].at[slots].set(jnp.asarray(rows))

    # ---------------- grow-on-demand grants + degradation ladder ------
    def _needs_block(self, r: Request) -> bool:
        """True when this tick's append row falls past the blocks the
        slot currently holds (decode crossed a block boundary)."""
        return len(r.blocks) < int(self.slot_len[r.slot]) \
            // self.block_len + 1

    def _grant(self, group: int) -> Optional[int]:
        """One-block grant from a sub-pool, through the chaos hook."""
        if self.grant_fault is not None and self.grant_fault():
            self.grant_denials += 1
            return None
        blk = self._alloc.allocate_one(group)
        if blk is None:
            self.grant_denials += 1
        return blk

    def _install_block(self, r: Request, blk: int) -> None:
        r.blocks.append(blk)
        self.cache["block_tbl"] = self.cache["block_tbl"].at[
            r.slot, len(r.blocks) - 1].set(blk)

    def _ensure_grants(self) -> None:
        """Grant admission: before a decode tick, every active slot must
        hold the block its append row lands in — a missing table entry
        would silently *drop* the append (the freed-slot contract) and
        corrupt the request.  Grant failures degrade down the ladder:
        spill a cold cached block to the host tier, else migrate the
        slot to an idling sub-pool, else preempt a victim (possibly the
        needy request itself) and retry.  After this returns, every
        remaining active slot can decode."""
        if self.kv_residency != "paged" or self.kv_admission != "grant":
            return
        for r in sorted(self.active.values(), key=lambda x: x.rid):
            guard = 0
            while self.active.get(r.slot) is r and self._needs_block(r):
                guard += 1
                assert guard <= self.max_batch + 2 * self.n_blocks + 2, \
                    "grant ladder did not converge"
                blk = self._grant(self._slot_group(r.slot))
                if blk is not None:
                    self._install_block(r, blk)
                    continue
                if self._spill_cold(self._slot_group(r.slot), 1):
                    continue
                if self._try_migrate(r):
                    continue
                self._preempt_for(r)

    # ---------------- copy-on-write barrier ---------------------------
    def _ensure_writable(self) -> None:
        """Before a decode tick, no slot may append into a block with
        refcount > 1 — writers never mutate shared state.  The natural
        flow keeps appends in private blocks (only *full* feed chunks
        are ever aliased, and the matched-token cap leaves the append
        column past them), so this barrier is the structural guarantee
        — and the path the forced-divergence test drives directly.  A
        CoW needs a fresh block; under pressure it degrades like a
        grant, by preempting a victim from the slot's sub-pool
        (migration is no help here — it refuses to move shared
        blocks)."""
        if self.kv_residency != "paged" or self._prefix is None:
            return
        if self._alloc.shared_blocks == 0:
            return
        for r in sorted(self.active.values(), key=lambda x: x.rid):
            guard = 0
            while self.active.get(r.slot) is r:
                col = int(self.slot_len[r.slot]) // self.block_len
                if col >= len(r.blocks):
                    break          # the grant ladder owns missing blocks
                blk = r.blocks[col]
                if self._alloc.refcount(blk) <= 1:
                    break
                guard += 1
                assert guard <= self.max_batch + 2 * self.n_blocks + 2, \
                    "CoW ladder did not converge"
                fresh = self._grant(self._slot_group(r.slot))
                if fresh is not None:
                    self._cow_copy(r, col, fresh)
                    break
                if self._spill_cold(self._slot_group(r.slot), 1):
                    continue
                self._preempt_for(r)

    def _cow_copy(self, r: Request, col: int, fresh: int) -> None:
        """Copy ``r``'s shared append block into ``fresh`` (its rows in
        every pool + table entry, one jitted gather-scatter) and drop
        this holder's
        reference to the original — the sharers keep it resident, trie
        entry and all."""
        old = r.blocks[col]
        with span("serve.cow", rid=r.rid):
            pools, tbl = self._cow_kernel(
                {key: self.cache[key] for key in self._pools},
                self.cache["block_tbl"], np.int32(old), np.int32(fresh),
                np.int32(r.slot), np.int32(col))
            self.cache.update(pools)
            self.cache["block_tbl"] = tbl
            r.blocks[col] = fresh
            self._release_blocks([old])
        self.cow_copies += 1

    def _try_migrate(self, r: Request) -> bool:
        """Rung 2: move ``r`` — blocks, table row, per-slot states — to
        a donor sub-pool that idles while its home pool is hot.  The
        donor must host a free slot (the batch dim is partitioned across
        data, so changing sub-pool means changing slot) and cover the
        current holding plus the block being asked for; the idlest such
        donor wins.  Preserves the slot→sub-pool combine contract: after
        the move every block the slot holds lives in its new data
        shard's sub-pool.

        Sharing-aware: a slot holding any *shared* block stays put —
        sharers' tables point at the original ids, and moving only this
        holder's copy would strand their aliases (shared blocks are
        pinned until their refcount drops back to 1)."""
        if self.pool_groups <= 1:
            return False
        if any(self._alloc.refcount(b) > 1 for b in r.blocks):
            return False
        src = self._slot_group(r.slot)
        need_now = len(r.blocks) + 1
        best = None
        for s2 in sorted(self.free_slots):
            g2 = self._slot_group(s2)
            if g2 == src or self._alloc.free_in(g2) < need_now:
                continue
            if best is None or self._alloc.free_in(g2) \
                    > self._alloc.free_in(self._slot_group(best)):
                best = s2
        if best is None:
            return False
        s1, s2 = r.slot, best
        g2 = self._slot_group(s2)
        new_blocks = self._alloc.allocate(need_now, g2)
        assert new_blocks is not None, "donor free count was just checked"
        old = list(r.blocks)
        if old:
            old_ids = jnp.asarray(old, jnp.int32)
            new_ids = jnp.asarray(new_blocks[:len(old)], jnp.int32)
            for key in self._pools:
                self.cache[key] = self._scatter_blocks(
                    self.cache[key], new_ids,
                    self._gather_blocks(self.cache[key], old_ids))
        for key in ("ssm", "conv"):
            if key in self.cache:
                self.cache[key] = self.cache[key].at[:, s2].set(
                    self.cache[key][:, s1])
        rows = np.full((int(self.cache["block_tbl"].shape[1]),), -1,
                       np.int32)
        rows[:need_now] = new_blocks
        tbl = self.cache["block_tbl"].at[s2].set(jnp.asarray(rows))
        self.cache["block_tbl"] = tbl.at[s1].set(-1)
        self._release_blocks(old)
        r.blocks = list(new_blocks)
        if self._prefix is not None and r.prefix_hashes:
            # the moved blocks hold the same content: re-key the trie
            # onto the new ids in the donor sub-pool (first writer wins,
            # so a still-resident original keeps its entry)
            self._prefix.insert(r.prefix_hashes,
                                r.blocks[:len(r.prefix_hashes)], g2)
        del self.active[s1]
        self.active[s2] = r
        r.slot = int(s2)
        self.free_slots.remove(s2)
        self.free_slots.append(s1)
        self.slot_len[s2] = self.slot_len[s1]
        self.slot_len[s1] = 0
        self.migrations += 1
        return True

    def _preempt_for(self, r: Request) -> None:
        """Rung 3: evict a victim from the needy slot's sub-pool so its
        grant can succeed (the victim may be the needy request itself,
        which also resolves the need).

        Sharing-aware: shared blocks are pinned — candidates holding
        the fewest shared blocks are preferred (evicting a sharer only
        drops a reference, freeing at most its private blocks, so
        victims whose eviction actually returns memory go first)."""
        group = self._slot_group(r.slot)
        cands = [a for a in self.active.values()
                 if self._slot_group(a.slot) == group]
        if self._prefix is not None and len(cands) > 1:
            def shared(a: Request) -> int:
                return sum(1 for b in a.blocks
                           if self._alloc.refcount(b) > 1)
            lo = min(shared(a) for a in cands)
            cands = [a for a in cands if shared(a) == lo]
        victim = self.preemption.pick_victim(cands, time.time())
        self._preempt(victim)

    def _preempt(self, r: Request) -> None:
        """Evict an active request to the host side.  With tiering on
        the victim *parks with state*: its KV blocks spill to the host
        tier (dense stripes and SSM/conv rows are copied host-side),
        so re-admission promotes them back and skips re-prefill
        entirely — token-identical resume, zero recompute.  Without
        tiering — or when the victim pins shared blocks, whose
        sharers' tables point at the old ids — blocks are released and
        re-admission is a re-prefill of prompt+generated.  Past the
        retry budget or an already-missed deadline the request is shed
        instead."""
        slot = r.slot
        del self.active[slot]
        r.slot = -1
        r.preemptions += 1
        self.preemptions += 1
        self._preempt_ticks.append(self.tick)
        shed_why = ""
        delay = 0
        if r.deadline is not None and time.time() > r.deadline:
            shed_why = ("deadline missed at preemption — a re-prefill "
                        "could not finish in time")
        else:
            pol = self._backoff.setdefault(
                r.rid, self.preemption.restart_policy())
            try:
                delay = int(pol.next_delay())
            except RuntimeError:
                shed_why = ("preemption retry budget exhausted "
                            f"({self.preemption.max_preemptions})")
        state = (self._park_state(r, slot)
                 if not shed_why and self.kv_tiering else None)
        if state is not None:
            self.free_slots.append(slot)
            self.slot_len[slot] = 0
            self.preempted.append(
                PreemptedRequest(r, self.tick + delay, state))
            return
        self._release_slot(slot, r)
        if shed_why:
            self._shed(r, shed_why)
            return
        self.preempted.append(PreemptedRequest(r, self.tick + delay))

    def _park_state(self, r: Request,
                    slot: int) -> Optional[Dict[str, Any]]:
        """Capture a victim's full per-slot state host-side so its
        resume needs no re-prefill: paged KV blocks spill to the host
        tier (ids stay on ``r.blocks``), dense stripes copy their valid
        rows, SSM/conv states copy their slot rows.  Returns None when
        the victim cannot park with state — it pins shared blocks
        (sharers' tables point at the old ids; moving them would strand
        every alias) or the host pool cannot cover its blocks even
        after evicting cold host entries — and the caller falls back to
        the legacy release+re-prefill park."""
        st: Dict[str, Any] = {"slot_len": int(self.slot_len[slot])}
        if self.kv_residency == "paged" and r.blocks:
            if self._host is None:
                return None
            if any(self._alloc.refcount(b) > 1 for b in r.blocks):
                return None
            short = len(r.blocks) - self._alloc.host_free
            if short > 0:
                self._evict_cached_host(short)
            if len(r.blocks) > self._alloc.host_free:
                return None
            # a parked victim's spilled blocks are private host copies
            # of *its* sequence — a trie match against them would alias
            # state the resume owns, so the entries go, not rekey
            if self._prefix is not None:
                self._prefix.evict(list(r.blocks))
            pairs = self._alloc.spill(list(r.blocks))
            assert pairs is not None, "host headroom was just checked"
            self._spill_rows(pairs)
            r.blocks = [h for _, h in pairs]
            st["kv_host"] = list(r.blocks)
            self.cache["block_tbl"] = \
                self.cache["block_tbl"].at[slot].set(-1)
        elif self.arch.has_attention:
            n = st["slot_len"]
            st["kv_rows"] = {key: np.asarray(self.cache[key][:, slot, :n])
                             for key in self._pools}
        for key in ("ssm", "conv"):
            if key in self.cache:
                st[key] = np.asarray(self.cache[key][:, slot])
        return st

    def preempt(self, rid: int) -> None:
        """Forcibly evict an active request (chaos/test hook and ops
        escape hatch; the engine preempts autonomously on grant
        failure)."""
        for r in self.active.values():
            if r.rid == rid:
                self._preempt(r)
                return
        raise KeyError(f"request {rid} is not active")

    def _shed(self, r: Request, why: str) -> None:
        assert not r.blocks, "shed request still holds blocks"
        r.error = why
        self.shed.append(r)
        self._backoff.pop(r.rid, None)

    def _shed_expired_pending(self) -> None:
        if not any(r.deadline is not None for r in self.pending):
            return
        now = time.time()
        keep: List[Request] = []
        for r in self.pending:
            if r.deadline is not None and now > r.deadline:
                self._shed(r, f"deadline missed while pending "
                              f"(tick {self.tick})")
            else:
                keep.append(r)
        self.pending = keep

    def _readmit_preempted(self) -> None:
        """Parked evictions whose backoff expired rejoin service.
        Stateless parks (tiering off, or a shared-block victim) rejoin
        the *front* of the pending queue (oldest rid first — they
        already burned a slot's worth of work; new arrivals should not
        starve them) and re-prefill.  Parked-with-state evictions skip
        the queue entirely: :meth:`_admit_resume` promotes their host
        blocks back into a free slot's sub-pool and decode continues
        where the eviction cut in — zero prefill calls.  A resume that
        cannot fit this tick stays parked and retries next tick."""
        if not self.preempted:
            return
        ready = [p for p in self.preempted if p.not_before_tick <= self.tick]
        if not ready:
            return
        keep = [p for p in self.preempted
                if p.not_before_tick > self.tick]
        for p in sorted(ready, key=lambda p: p.request.rid, reverse=True):
            if p.parked_state is None:
                self.pending.insert(0, p.request)
                continue
            r = p.request
            if r.deadline is not None and time.time() > r.deadline:
                self._drop_parked(p)
                self._shed(r, f"deadline missed while parked "
                              f"(tick {self.tick})")
                continue
            if not self._admit_resume(p):
                keep.append(p)
        self.preempted = keep

    def _admit_resume(self, p: PreemptedRequest) -> bool:
        """Resume a parked-with-state eviction: promote its host KV
        blocks into a free slot's sub-pool (consuming the prefetch
        stage if it landed), restore dense/SSM/conv rows, and hand the
        request straight back to decode.  No prefill call — the next
        tick feeds the last generated token at the parked position, so
        the continuation is token-identical to an uninterrupted run."""
        r, st = p.request, p.parked_state
        if not self.free_slots:
            return False
        host_ids = st.get("kv_host", [])
        if host_ids:
            # the free slot whose sub-pool can cover the promote wins
            # (emptiest first); spill cold cached blocks to make room
            slot = None
            for s in sorted(self.free_slots,
                            key=lambda s: (-self._alloc.free_in(
                                self._slot_group(s)), s)):
                g = self._slot_group(s)
                short = len(host_ids) - self._alloc.free_in(g)
                if short > 0:
                    self._spill_cold(g, short)
                if self._alloc.free_in(g) >= len(host_ids):
                    slot = s
                    break
            if slot is None:
                return False
            g = self._slot_group(slot)
            staged = self._staged.pop(r.rid, None)
            pairs = self._alloc.promote(host_ids, g)
            assert pairs is not None, "free count was just checked"
            if staged is not None and staged[0] == tuple(host_ids):
                self._promote_rows(pairs, staged[1])
            else:
                self._promote_rows(pairs)
            r.blocks = [b for _, b in pairs]
            rows = np.full((int(self.cache["block_tbl"].shape[1]),), -1,
                           np.int32)
            rows[:len(r.blocks)] = r.blocks
            self.cache["block_tbl"] = \
                self.cache["block_tbl"].at[slot].set(jnp.asarray(rows))
            if self._prefix is not None and r.prefix_hashes:
                # back on HBM, the prefix blocks are shareable again
                self._prefix.insert(r.prefix_hashes,
                                    r.blocks[:len(r.prefix_hashes)], g)
        else:
            slot = min(self.free_slots)
            n = st["slot_len"]
            for key, rows in st.get("kv_rows", {}).items():
                self.cache[key] = self.cache[key].at[:, slot, :n].set(
                    jnp.asarray(rows))
        for key in ("ssm", "conv"):
            if key in st:
                self.cache[key] = self.cache[key].at[:, slot].set(
                    jnp.asarray(st[key]))
        self.free_slots.remove(slot)
        self.slot_len[slot] = st["slot_len"]
        r.slot = int(slot)
        self.active[slot] = r
        return True

    def _drop_parked(self, p: PreemptedRequest) -> None:
        """Release a parked-with-state eviction's host-side holdings
        (shed, or abandoned): host block refs return to the host free
        list and any staged prefetch is discarded."""
        r = p.request
        self._staged.pop(r.rid, None)
        if p.parked_state and p.parked_state.get("kv_host"):
            freed = self._alloc.release(r.blocks)
            if self._prefix is not None and freed:
                self._prefix.evict(freed)
            r.blocks = []

    def _stage_prefetch(self) -> None:
        """Double-buffered resume prefetch: for every parked-with-state
        eviction whose backoff expires by the *next* tick, start the
        host→device transfer of its spilled KV rows now
        (``jax.device_put``) — this tick's decode dispatch overlaps the
        stream-in, and the resume finds device-resident rows waiting
        instead of paying a synchronous copy.  One-tick lookahead is
        what the plan's feasibility check sized: a block must stream in
        under ``block_len`` decode ticks.  ``kv_prefetch="off"``
        disables staging — the resume stalls on the transfer (the gap
        the benchmark's prefetch-off rows measure)."""
        if not (self.kv_prefetch and self.kv_tiering
                and self._host is not None):
            return
        for p in self.preempted:
            st = p.parked_state
            if st is None or not st.get("kv_host"):
                continue
            if p.not_before_tick > self.tick + 1:
                continue
            rid = p.request.rid
            ids = tuple(st["kv_host"])
            got = self._staged.get(rid)
            if got is not None and got[0] == ids:
                continue
            idx = np.asarray([h - self.n_blocks for h in ids], np.int64)
            with span("serve.promote", blocks=len(ids), rid=rid):
                self._staged[rid] = (ids, {
                    key: jax.device_put(self._host[key][:, idx])
                    for key in self._pools})

    # ------------------------------------------------------------------
    def _next_key(self) -> jax.Array:
        self._key, sub = jax.random.split(self._key)
        return sub

    def _sample(self, r: Request, logits: jax.Array, key: jax.Array) -> int:
        """Request ``r``'s next token from its logits row.  ``serve_sample``
        applies the same rule to every row at once; probes override this
        hook, and an engine whose hook is not this function calls it for
        every emitted token."""
        logits = logits[:self.arch.vocab_size].astype(jnp.float32)
        if r.temperature <= 0:
            return int(jnp.argmax(logits))
        return int(jax.random.categorical(key, logits / r.temperature))

    _own_sample = _sample

    def _samples_batched(self) -> bool:
        """True when ``_sample`` is the engine's own rule, which
        ``serve_sample`` batches: an override on a subclass, on the
        class or on the instance needs every row on the host."""
        return (getattr(self._sample, "__func__", None)
                is ServeEngine._own_sample)

    def _draw(self, logits: jax.Array, keys: jax.Array,
              rows: Dict[int, Request], batched: bool,
              stats: Optional[Dict[str, jax.Array]] = None,
              phase: str = "", live=()) -> Dict[int, int]:
        """The next token of each request in ``rows`` (logits row index
        -> request), with ``keys[i]`` for row ``i``.  Batched: one
        ``serve_sample`` over every row of ``logits`` (rows not in
        ``rows`` are drawn greedily and discarded) and one transfer;
        otherwise ``_sample`` row by row, in ``rows``' order.

        ``stats`` are the counters of the program that made ``logits``:
        its per-row ``moe_held_pairs`` ride home in the same transfer
        and the rows in ``live`` add to ``self.moe_held_pairs[phase]``."""
        counts = (stats or {}).get("moe_held_pairs")
        if not rows or not batched:
            if counts is not None:
                self._count_pairs(phase, np.asarray(counts), live)
            if not rows:
                return {}
            self.sampled_per_slot += len(rows)
            return {i: int(self._sample(r, logits[i], keys[i]))
                    for i, r in rows.items()}
        temps = np.zeros((logits.shape[0],), np.float32)
        for i, r in rows.items():
            temps[i] = r.temperature
        if keys.shape[0] < logits.shape[0]:
            # a padded prefill batch: dummy rows need a key of any value
            keys = jnp.pad(keys, ((0, logits.shape[0] - keys.shape[0]),
                                  (0, 0)))
        extra = {} if counts is None else {"counts": counts}
        toks = np.asarray(self._sample_rows(
            logits, temps, keys, sampled=bool((temps > 0).any()), **extra))
        if counts is not None:
            toks, got = toks
            self._count_pairs(phase, got, live)
        self.sampled_batched += len(rows)
        return {i: int(toks[i]) for i in rows}

    def _count_pairs(self, phase: str, counts: np.ndarray, live) -> None:
        """Add the ``live`` rows' held pairs to the counter, and mark
        them on the trace (``serve.moe``, an instant whose ``pairs``
        and ``phase`` the benchmark's readers sum per tick)."""
        n = int(counts[list(live)].sum())
        self.moe_held_pairs[phase] += n
        with span("serve.moe", phase=phase, pairs=n):
            pass

    def _count_decode_blocks(self) -> int:
        """Add this tick's decode reads to the block counters: each busy
        slot's ``ceil((len + 1) / block_len)`` live blocks (the row it
        appends included), against every table entry.  Returns the live
        count (0 for a dense cache)."""
        if self.kv_residency != "paged":
            return 0
        bl = self.block_len
        live = sum(int(self.slot_len[s] + bl) // bl for s in self.active)
        self.decode_blocks_live += live
        self.decode_blocks_table += self.cache["block_tbl"].size
        return live

    def _sync_pos(self) -> None:
        """Mirror per-slot lengths into the device cache (freed slots 0)."""
        pos = jnp.asarray(self.slot_len)
        if self._pos_sharding is not None:
            pos = jax.device_put(pos, self._pos_sharding)
        self.cache["pos"] = pos

    def step(self) -> int:
        """One engine tick: shed expired, re-admit parked evictions,
        admit, secure grants, decode one token for all active slots."""
        self.tick += 1
        with span("serve.step", tick=self.tick):
            if self.kv_residency == "paged" and \
                    self.tick % self.preemption.shed_window_ticks == 0:
                # new low-water epoch once per rebalance window: without
                # the reset the watermark only ever ratchets down, so one
                # transient dip reads as a permanently hot sub-pool forever
                self._alloc.reset_low_water()
            self._poll_disagg()
            self._shed_expired_pending()
            self._shed_expired_flights()
            with span("serve.readmit"):
                self._readmit_preempted()
            with span("serve.admit"):
                self._admit()
            with span("serve.grant"):
                self._ensure_grants()
                self._ensure_writable()
            # stage next tick's resume transfers before dispatching this
            # tick's decode: the async device_put streams in underneath it
            self._stage_prefetch()
            if not self.active:
                if self._disagg:
                    # only flights in play: workers are computing
                    # off-process; don't spin the tick counter at memory
                    # speed waiting
                    time.sleep(0.01)
                return 0
            live = self._count_decode_blocks()
            with span("serve.decode", slots=len(self.active),
                      live_blocks=live):
                # per-slot positions: every slot decodes at its own
                # offset.  Freed slots are masked to (token 0, pos 0):
                # their decode is a bounded dummy over one cache row, so
                # stale KV / stale last-token garbage never reaches a
                # live slot's logits.
                self._sync_pos()
                tokens = np.zeros((self.max_batch, 1), np.int32)
                for slot, r in self.active.items():
                    # a ride-admitted request has no output yet: its
                    # first tick feeds the last prompt token (the one
                    # admission left unaliased) and samples the first
                    # output
                    tokens[slot, 0] = (r.out_tokens[-1] if r.out_tokens
                                       else int(r.feed_tokens[-1]))
                logits, self.cache, stats = self._decode(
                    self.params, self.cache, {"tokens": jnp.asarray(tokens)})
            # the first sample also waits for the decode dispatched above
            batched = self._samples_batched()
            with span("serve.sample", slots=len(self.active),
                      rids=[r.rid for r in self.active.values()],
                      batched=batched):
                slot_keys = jax.random.split(self._next_key(),
                                             self.max_batch)
                toks = self._draw(logits, slot_keys, dict(self.active),
                                  batched, stats, "decode",
                                  list(self.active))
                finished = []
                for slot, r in list(self.active.items()):
                    r.out_tokens.append(toks[slot])
                    if r.t_first == 0.0:       # first token via decode-ride
                        r.t_first = time.time()
                    self.slot_len[slot] += 1
                    if len(r.out_tokens) >= r.max_new_tokens:
                        r.done = True
                        r.t_done = time.time()
                        finished.append(r)
                        self.finished.append(r)
                        del self.active[slot]
                        self._release_slot(slot, r)
                        self._backoff.pop(r.rid, None)
            return len(finished)

    def _release_slot(self, slot: int, r: Request) -> None:
        """Return the slot — and, when paged, its blocks — to the pool.

        This is real reclamation: the block ids go back on their
        sub-pool's free list and the table row is cleared to -1, so the
        freed slot's decode dummy neither writes to the pool (unassigned
        appends drop) nor pins memory the next admission could use.
        """
        self.free_slots.append(slot)
        self.slot_len[slot] = 0
        if self.kv_residency == "paged" and r.blocks:
            self._release_blocks(r.blocks)
            r.blocks = []
            self.cache["block_tbl"] = \
                self.cache["block_tbl"].at[slot].set(-1)

    def run_until_idle(self, max_ticks: int = 1000) -> List[Request]:
        """Tick until no live work remains (parked evictions count as
        live — their backoff just hasn't expired).  Raises a loud
        :class:`TimeoutError` naming the stuck request ids when work
        remains after ``max_ticks``: a deadlocked admission loop must
        not be indistinguishable from success."""
        ticks = 0
        while self.pending or self.active or self.preempted or self._disagg:
            if ticks >= max_ticks:
                stuck = sorted(
                    [r.rid for r in self.pending]
                    + [r.rid for r in self.active.values()]
                    + [p.request.rid for p in self.preempted]
                    + list(self._disagg.keys()))
                raise TimeoutError(
                    f"run_until_idle: {len(stuck)} request(s) still live "
                    f"after {max_ticks} ticks (pending={len(self.pending)} "
                    f"active={len(self.active)} "
                    f"preempted={len(self.preempted)} "
                    f"disagg={len(self._disagg)}): rids {stuck}")
            self.step()
            ticks += 1
        return self.finished
