"""Disaggregated prefill: a supervised worker fleet behind the engine.

The paper's flow specializes one memory template per *role*; prefill
and decode are different roles with opposite profiles (a flops-bound
burst over the whole prompt vs a bandwidth-bound tick over one token),
so when the plan's interference model says an inline prefill would
steal too many decode ticks (``kv_prefill_mode: disagg``), prefill
moves out of the engine process entirely:

* :func:`_worker_main` — the prefill worker.  Spawned (never forked —
  the parent's JAX runtime does not survive a fork), it rebuilds the
  cache geometry from the *same* :class:`~repro.core.plan.FrozenPlan`
  JSON the engine holds and proves it at handshake: the first message
  home is its recomputed plan content hash, and a mismatch is a typed
  :class:`PlanHandshakeError` on the orchestrator side — two processes
  disagreeing about block geometry must never exchange KV bytes.
  Prompts prefill **chunked block-native** via
  :func:`repro.models.lm.prefill_chunked`: each ``block_len``-sized
  chunk is one pool-block-shaped KV slab streamed home as soon as it
  exists (no dense ``(B, plen)`` intermediate), with a heartbeat after
  every chunk.

* :class:`PrefillFleet` — the host-side supervisor.  Dispatches
  prompts to the least-loaded live worker, feeds heartbeats into
  :class:`repro.runtime.fault.HealthMonitor` (workers are
  ``expect()``-registered at spawn, so a dead-on-arrival worker is
  detected, not invisible), detects death by both liveness probe and
  heartbeat deadline, respawns under a per-slot
  :class:`~repro.runtime.fault.RestartPolicy` exponential backoff, and
  reports the in-flight request ids a death orphaned so the engine can
  re-dispatch them from its chunk journal.  A slot whose restart
  budget is exhausted retires; when every slot has retired the fleet
  raises its :class:`DegradedMode` flag and the engine falls back to
  in-process prefill — degraded, never crashed.
"""

from __future__ import annotations

import dataclasses
import queue as _queue
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from repro.runtime.fault import HealthMonitor, RestartPolicy


class PlanHandshakeError(RuntimeError):
    """A prefill worker's recomputed FrozenPlan content hash does not
    match the engine's — the two sides would build different cache
    geometry, so no KV block may cross the wire."""


@dataclasses.dataclass(frozen=True)
class DegradedMode:
    """Typed degraded state: the fleet is gone and prefill runs
    in-process again.  Surfaced through ``pressure_stats()`` /
    ``telemetry()`` so operators see *that* and *why* the engine
    degraded instead of inferring it from latency."""

    reason: str
    worker_deaths: int
    restarts: int
    at_tick: int = -1              # stamped by the engine when observed

    def to_json(self) -> Dict[str, Any]:
        return {"reason": self.reason,
                "worker_deaths": int(self.worker_deaths),
                "restarts": int(self.restarts),
                "at_tick": int(self.at_tick)}


def _worker_main(wid: int, inq, outq, payload: Dict[str, Any]) -> None:
    """Prefill worker entry point (spawn target; must be importable).

    Protocol (worker -> orchestrator, all through ``outq``):
      ``("hello", wid, plan_hash)``      handshake, first message
      ``("beat", wid, t)``               heartbeat (idle and per chunk)
      ``("chunk", wid, rid, idx, rows)`` one pool-block-shaped slab of
                                         each cache pool (``{pool: rows}``)
      ``("done", wid, rid, logits)``     last-token logits, prompt done
      ``("error", wid, rid, msg)``       prefill raised (typed, not a crash)

    Instructions (orchestrator -> worker, through ``inq``):
      ``("prefill", rid, tail_tokens, prefix)`` (``{pool: rows}`` or None)
      ``("stop",)``
    """
    import jax

    from repro.core.passes.lowering import build_run_cfg
    from repro.core.plan import FrozenPlan
    from repro.models import lm

    plan = FrozenPlan.from_json(payload["plan_json"])
    got = plan.content_hash()
    outq.put(("hello", wid, got))
    if got != payload["plan_hash"]:
        return                      # the orchestrator raises; we just leave
    arch = payload["arch"]
    cfg = build_run_cfg(plan, arch, None)
    params = payload["params"]
    bl, kvh = payload["block_len"], payload["kv_heads"]
    hb, delay = payload["heartbeat_s"], payload["chunk_delay_s"]
    # one long-lived jit so the per-(prefix, tail) shape compile cache
    # survives across prompts
    tail_fn = jax.jit(
        lambda p, b, pre: lm.prefill_tail(arch, p, b, cfg, pre))
    while True:
        try:
            msg = inq.get(timeout=hb)
        except _queue.Empty:
            outq.put(("beat", wid, time.time()))
            continue
        if msg[0] == "stop":
            return
        _, rid, tokens, prefix = msg

        def on_chunk(idx, rows, _rid=rid):
            if delay:
                time.sleep(delay)   # chaos knob: widen the kill window
            outq.put(("chunk", wid, _rid, idx,
                      {k: np.asarray(v) for k, v in rows.items()}))
            outq.put(("beat", wid, time.time()))

        try:
            logits, _ = lm.prefill_chunked(
                arch, params, tokens, bl, cfg, kv_heads=kvh,
                prefix=prefix, on_chunk=on_chunk, tail_fn=tail_fn)
            outq.put(("done", wid, rid, np.asarray(logits)))
        except Exception as e:      # noqa: BLE001 — typed event, no crash
            outq.put(("error", wid, rid, f"{type(e).__name__}: {e}"))


@dataclasses.dataclass
class _WorkerSlot:
    """One supervised worker position: a process incarnation chain
    under a restart budget.  Worker ids are unique per incarnation so a
    late message from a killed predecessor can never impersonate its
    replacement."""

    idx: int
    policy: RestartPolicy
    proc: Any = None
    inq: Any = None
    wid: int = -1
    incarnation: int = 0
    ready: bool = False            # hello received (hash verified)
    retired: bool = False          # restart budget exhausted
    retire_reason: str = ""
    respawn_at: float = 0.0
    inflight: List[int] = dataclasses.field(default_factory=list)


class PrefillFleet:
    """Supervisor for N prefill worker processes (see module docstring).

    The fleet is transport-complete but policy-free: it spawns,
    handshakes, dispatches, detects death, respawns with backoff, and
    retires exhausted slots — what to *do* about an orphaned request
    (the chunk journal, the resume boundary, degraded fallback) is the
    engine's call, driven by the events :meth:`poll` returns.
    """

    def __init__(self, plan, arch, params, n_workers: int = 1, *,
                 block_len: int, kv_heads: int = 0,
                 heartbeat_s: float = 2.0,
                 heartbeat_timeout_s: float = 60.0,
                 max_restarts: int = 4,
                 backoff_base_s: float = 0.25,
                 backoff_cap_s: float = 5.0,
                 chunk_delay_s: float = 0.0,
                 hello_timeout_s: float = 300.0,
                 start: bool = True,
                 _expect_hash: Optional[str] = None):
        if n_workers < 1:
            raise ValueError(f"n_workers must be >= 1, got {n_workers}")
        import multiprocessing as mp
        # fork after JAX initialization deadlocks; spawn re-imports
        self._ctx = mp.get_context("spawn")
        self._outq = self._ctx.Queue()
        self.n_workers = n_workers
        self.expected_hash = _expect_hash or plan.content_hash()
        self._payload = {
            "plan_json": plan.to_json(),
            "plan_hash": self.expected_hash,
            "arch": arch,
            "params": _to_numpy(params),
            "block_len": int(block_len),
            "kv_heads": int(kv_heads),
            "heartbeat_s": float(heartbeat_s),
            "chunk_delay_s": float(chunk_delay_s),
        }
        self.monitor = HealthMonitor(timeout_s=heartbeat_timeout_s)
        self._hello_timeout_s = hello_timeout_s
        self._slots = [
            _WorkerSlot(idx=i, policy=RestartPolicy(
                max_restarts=max_restarts,
                backoff_base_s=backoff_base_s,
                backoff_cap_s=backoff_cap_s))
            for i in range(n_workers)]
        self._wid2slot: Dict[int, _WorkerSlot] = {}
        self._assign: Dict[int, _WorkerSlot] = {}      # rid -> slot
        self.dispatches = 0
        self.deaths = 0
        self.restarts = 0
        self.errors = 0
        self.degraded: Optional[DegradedMode] = None
        self._started = False
        if start:
            self.start()

    # ------------------------------------------------------------------
    def _spawn(self, slot: _WorkerSlot) -> None:
        slot.incarnation += 1
        slot.wid = slot.idx + self.n_workers * slot.incarnation
        slot.inq = self._ctx.Queue()
        slot.ready = False
        slot.proc = self._ctx.Process(
            target=_worker_main,
            args=(slot.wid, slot.inq, self._outq, self._payload),
            daemon=True)
        slot.proc.start()
        self._wid2slot[slot.wid] = slot
        self.monitor.expect([slot.wid])

    def start(self) -> None:
        """Spawn every slot and block until each live worker's hello
        verifies the plan hash (mismatch: :class:`PlanHandshakeError`).
        A worker that dies before hello is left to the restart path."""
        if self._started:
            return
        self._started = True
        for slot in self._slots:
            self._spawn(slot)
        deadline = time.time() + self._hello_timeout_s
        while time.time() < deadline:
            if all(s.ready or s.proc is None or not s.proc.is_alive()
                   for s in self._slots):
                return
            try:
                msg = self._outq.get(timeout=0.2)
            except _queue.Empty:
                continue
            self._handle(msg, [])

    # ------------------------------------------------------------------
    def dispatch(self, rid: int, tokens, prefix=None) -> bool:
        """Send one prompt (tail tokens past any journaled prefix) to
        the least-loaded live worker.  ``False`` when no worker is
        live right now (all between death and respawn, or retired) —
        the caller retries next poll or degrades.  ``prefix`` maps
        each cache pool to the journaled rows a resume starts after."""
        live = [s for s in self._slots
                if not s.retired and s.proc is not None
                and s.proc.is_alive()]
        if not live:
            return False
        slot = min(live, key=lambda s: (len(s.inflight), s.idx))
        tokens = np.asarray(tokens, np.int32)
        slot.inq.put(("prefill", rid, tokens,
                      None if prefix is None else
                      {k: np.asarray(v) for k, v in prefix.items()}))
        slot.inflight.append(rid)
        self._assign[rid] = slot
        self.dispatches += 1
        return True

    def cancel(self, rid: int) -> None:
        """Forget a request (shed/aborted engine-side).  The worker may
        still burn compute on it; its late events are dropped here."""
        slot = self._assign.pop(rid, None)
        if slot is not None and rid in slot.inflight:
            slot.inflight.remove(rid)

    def kill_worker(self, idx: Optional[int] = None,
                    rid: Optional[int] = None) -> bool:
        """Chaos hook: SIGKILL a live worker — by slot index, by the
        request it is running (``rid``), or any live one."""
        slot = None
        if rid is not None:
            slot = self._assign.get(rid)
        elif idx is not None:
            slot = self._slots[idx]
        else:
            for s in self._slots:
                if s.proc is not None and s.proc.is_alive():
                    slot = s
                    break
        if slot is None or slot.proc is None or not slot.proc.is_alive():
            return False
        slot.proc.kill()
        slot.proc.join(timeout=30)
        return True

    # ------------------------------------------------------------------
    def _handle(self, msg, events: List[Tuple]) -> None:
        kind, wid = msg[0], msg[1]
        slot = self._wid2slot.get(wid)
        if slot is None or slot.wid != wid:
            return                  # stale incarnation: drop
        self.monitor.beat(wid)
        if kind == "hello":
            got = msg[2]
            if got != self.expected_hash:
                self.shutdown()
                raise PlanHandshakeError(
                    f"worker {wid} rebuilt the plan with content hash "
                    f"{got[:12]}… but the engine expects "
                    f"{self.expected_hash[:12]}… — mismatched cache "
                    "geometry; refusing to exchange KV blocks")
            slot.ready = True
        elif kind == "chunk":
            _, _, rid, idx, rows = msg
            if rid in self._assign:
                events.append(("chunk", rid, idx, rows))
        elif kind == "done":
            _, _, rid, logits = msg
            if rid in self._assign:
                self.cancel(rid)
                events.append(("done", rid, logits))
        elif kind == "error":
            _, _, rid, err = msg
            self.errors += 1
            if rid in self._assign:
                self.cancel(rid)
                events.append(("error", rid, err))
        # "beat" needs nothing beyond the monitor feed above

    def poll(self) -> List[Tuple]:
        """Drain worker messages and supervise the fleet.  Returns
        engine-facing events: ``("chunk", rid, idx, rows)``,
        ``("done", rid, logits)``, ``("error", rid, msg)``, and
        ``("dead", rid)`` for every request a worker death orphaned.
        Also respawns due slots and raises the degraded flag when the
        whole fleet has retired."""
        events: List[Tuple] = []
        while True:
            try:
                msg = self._outq.get_nowait()
            except _queue.Empty:
                break
            self._handle(msg, events)
        now = time.time()
        hung = set(self.monitor.dead_hosts(now))
        for slot in self._slots:
            if slot.retired or slot.proc is None:
                continue
            if slot.proc.is_alive() and slot.wid not in hung:
                continue
            # death: liveness probe failed, or heartbeat deadline passed
            self.deaths += 1
            if slot.proc.is_alive():
                slot.proc.kill()    # hung-alive: put it out of its misery
            slot.proc.join(timeout=30)
            self.monitor.forget(slot.wid)
            self._wid2slot.pop(slot.wid, None)
            slot.proc = None
            for rid in slot.inflight:
                self._assign.pop(rid, None)
                events.append(("dead", rid))
            slot.inflight = []
            try:
                slot.respawn_at = now + slot.policy.next_delay()
            except RuntimeError as e:   # budget exhausted: retire
                slot.retired = True
                slot.retire_reason = str(e)
        for slot in self._slots:
            if slot.proc is None and not slot.retired \
                    and now >= slot.respawn_at:
                self._spawn(slot)
                self.restarts += 1
        if self.degraded is None and all(s.retired for s in self._slots):
            self.degraded = DegradedMode(
                reason=(f"all {self.n_workers} prefill worker slot(s) "
                        "exhausted their restart budget "
                        f"({self._slots[0].policy.max_restarts} each)"),
                worker_deaths=self.deaths,
                restarts=self.restarts)
        return events

    # ------------------------------------------------------------------
    def stats(self) -> Dict[str, Any]:
        """JSON-serializable fleet snapshot (telemetry building block)."""
        live = sum(1 for s in self._slots
                   if s.proc is not None and s.proc.is_alive())
        return {"workers": self.n_workers,
                "live": live,
                "retired": sum(1 for s in self._slots if s.retired),
                "dispatches": self.dispatches,
                "deaths": self.deaths,
                "restarts": self.restarts,
                "errors": self.errors,
                "inflight": len(self._assign),
                "degraded": (self.degraded.to_json()
                             if self.degraded is not None else None)}

    def shutdown(self) -> None:
        """Stop every worker (graceful stop, then SIGKILL stragglers)."""
        for slot in self._slots:
            if slot.proc is None:
                continue
            try:
                slot.inq.put(("stop",))
            except Exception:       # noqa: BLE001 — queue may be broken
                pass
        for slot in self._slots:
            if slot.proc is None:
                continue
            slot.proc.join(timeout=5)
            if slot.proc.is_alive():
                slot.proc.kill()
                slot.proc.join(timeout=5)
            self.monitor.forget(slot.wid)
            slot.proc = None
        self._assign.clear()


def _to_numpy(params):
    """Host-side copy of a params pytree (pickled into worker spawns)."""
    import jax
    return jax.tree.map(np.asarray, params)
