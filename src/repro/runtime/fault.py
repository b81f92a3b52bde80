"""Fault tolerance: failure detection, restart policy, elastic re-mesh.

At 1000+ nodes the mean time between node failures drops below the job
length; the runtime must (a) detect, (b) checkpoint-restart, (c) continue
on a *different* device count when replacements lag.  The pieces:

* :class:`HealthMonitor`   — heartbeat table + deadline detection.
* :class:`RestartPolicy`   — exponential backoff, max-restarts budget.
* :func:`elastic_mesh`     — largest (data', model) mesh that fits the
  surviving devices while preserving the model axis (TP must not shrink
  below what the weights were planned for; data/pod axes absorb losses).
* :class:`StepGuard`       — wraps the train step: on any device error it
  restores from the last checkpoint and replays the data stream (the
  pipeline is seeded per (host, step), so replay is bit-exact).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax


@dataclasses.dataclass
class HealthMonitor:
    """Heartbeat bookkeeping (transport-agnostic: callers feed beats).

    Hosts must be *registered* with :meth:`expect` before they are
    trusted to beat: a worker that dies between spawn and its first
    heartbeat never enters ``beats``, and a monitor that only scans
    ``beats`` reports it healthy forever.  ``expect`` starts the
    deadline clock at registration time, so dead-on-arrival hosts show
    up in :meth:`dead_hosts` after the same ``timeout_s`` as a host
    that beat once and went silent.
    """

    timeout_s: float = 60.0
    beats: Dict[int, float] = dataclasses.field(default_factory=dict)
    expected: Dict[int, float] = dataclasses.field(default_factory=dict)

    def expect(self, host_ids, t: Optional[float] = None) -> None:
        """Register hosts that *should* beat; resets their deadline clock
        (re-registering a respawned host id restarts its grace window)."""
        now = t if t is not None else time.time()
        for h in host_ids:
            self.expected[h] = now
            self.beats.pop(h, None)

    def forget(self, host_id: int) -> None:
        """Deregister a host (retired/shut down on purpose)."""
        self.expected.pop(host_id, None)
        self.beats.pop(host_id, None)

    def beat(self, host_id: int, t: Optional[float] = None) -> None:
        self.beats[host_id] = t if t is not None else time.time()

    def dead_hosts(self, now: Optional[float] = None) -> List[int]:
        now = now if now is not None else time.time()
        dead = {h for h, t in self.beats.items() if now - t > self.timeout_s}
        # dead-on-arrival: expected, never beat, grace window elapsed
        dead |= {h for h, t0 in self.expected.items()
                 if h not in self.beats and now - t0 > self.timeout_s}
        return sorted(dead)

    def healthy(self, now: Optional[float] = None) -> bool:
        return not self.dead_hosts(now)


@dataclasses.dataclass
class RestartPolicy:
    max_restarts: int = 100
    backoff_base_s: float = 5.0
    backoff_cap_s: float = 300.0
    restarts: int = 0

    def next_delay(self) -> float:
        if self.restarts >= self.max_restarts:
            raise RuntimeError(
                f"restart budget exhausted ({self.max_restarts})")
        d = min(self.backoff_base_s * (2 ** min(self.restarts, 10)),
                self.backoff_cap_s)
        self.restarts += 1
        return d


def elastic_mesh(n_devices: int, model_parallel: int,
                 axis_names: Tuple[str, ...] = ("data", "model")):
    """Largest (data', model) mesh on the surviving devices.

    The model axis is preserved (the memory plan's TP sharding of the
    weights is only valid at that width); whole TP groups that lost a
    member are dropped, so data parallelism absorbs the failure.
    """
    data = n_devices // model_parallel
    if data < 1:
        raise RuntimeError(
            f"{n_devices} devices cannot host model_parallel="
            f"{model_parallel}")
    usable = data * model_parallel
    devices = jax.devices()[:usable]
    if len(devices) < usable:
        raise RuntimeError(
            f"only {len(devices)} device(s) visible; need {usable} "
            f"(data={data} x model_parallel={model_parallel}) — "
            f"shrink n_devices to what actually survived")
    from repro.launch.mesh import make_mesh
    return make_mesh((data, model_parallel), axis_names, devices=devices)


class StepGuard:
    """Run steps; on device failure restore + replay.

    ``make_step(mesh) -> (step_fn, state)`` rebuilds the jitted step for a
    (possibly smaller) mesh; ``restore(mesh) -> (state, step)`` reloads
    the latest checkpoint resharded for it.
    """

    def __init__(self, make_step: Callable, restore: Callable,
                 policy: Optional[RestartPolicy] = None,
                 model_parallel: int = 1):
        self.make_step = make_step
        self.restore = restore
        self.policy = policy or RestartPolicy()
        self.model_parallel = model_parallel
        self.events: List[Dict] = []

    def run(self, state, batches, n_steps: int, start_step: int = 0,
            fail_injector: Optional[Callable[[int], None]] = None):
        """Drive n_steps; inject failures in tests via fail_injector."""
        mesh = elastic_mesh(len(jax.devices()), self.model_parallel)
        step_fn = self.make_step(mesh)
        step = start_step
        metrics = None
        while step < n_steps:
            try:
                if fail_injector is not None:
                    fail_injector(step)
                batch = batches(step)
                state, metrics = step_fn(state, batch)
                step += 1
            except (RuntimeError, jax.errors.JaxRuntimeError) as e:
                delay = self.policy.next_delay()
                self.events.append({"step": step, "error": str(e)[:200],
                                    "backoff_s": delay})
                # (in production: sleep(delay); wait for healthy quorum)
                mesh = elastic_mesh(len(jax.devices()), self.model_parallel)
                step_fn = self.make_step(mesh)
                state, step = self.restore(mesh)
        return state, step, metrics
