"""Fault tolerance, stragglers, compression collectives, hlo_stats."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.dist.collectives import compressed_psum, ef_compress
from repro.launch.mesh import make_mesh
from repro.runtime import (DeadlineSkipper, HealthMonitor, RestartPolicy,
                           StepTimer, elastic_mesh)


def test_health_monitor():
    hm = HealthMonitor(timeout_s=10)
    hm.beat(0, t=100.0)
    hm.beat(1, t=105.0)
    assert hm.dead_hosts(now=109.0) == []
    assert hm.dead_hosts(now=112.0) == [0]
    assert not hm.healthy(now=130.0)


def test_health_monitor_expect_flags_dead_on_arrival():
    """A worker that dies between spawn and its first heartbeat must
    still show up dead: ``expect`` starts the deadline clock, so a
    beats-only scan can't report it healthy forever."""
    hm = HealthMonitor(timeout_s=10)
    hm.expect([7], t=100.0)
    assert hm.dead_hosts(now=105.0) == []      # still in its grace window
    assert hm.dead_hosts(now=111.0) == [7]     # never beat: DOA
    hm.beat(7, t=112.0)
    assert hm.dead_hosts(now=120.0) == []      # late first beat clears it
    hm.expect([7], t=200.0)                    # respawn: stale beat dropped
    assert hm.dead_hosts(now=205.0) == []
    assert hm.dead_hosts(now=211.0) == [7]
    hm.forget(7)
    assert hm.dead_hosts(now=500.0) == []      # retired on purpose


def test_restart_policy_backoff_and_budget():
    rp = RestartPolicy(max_restarts=3, backoff_base_s=1.0, backoff_cap_s=10)
    ds = [rp.next_delay() for _ in range(3)]
    assert ds == [1.0, 2.0, 4.0]
    with pytest.raises(RuntimeError):
        rp.next_delay()


def test_restart_policy_zero_budget_and_cap():
    """max_restarts=0 refuses the first restart (the degrade-now
    config); the backoff series clamps at the cap instead of doubling
    unbounded."""
    with pytest.raises(RuntimeError, match="restart budget exhausted"):
        RestartPolicy(max_restarts=0).next_delay()
    rp = RestartPolicy(max_restarts=6, backoff_base_s=1.0, backoff_cap_s=3.0)
    assert [rp.next_delay() for _ in range(6)] == [1.0, 2.0, 3.0, 3.0,
                                                  3.0, 3.0]


def test_elastic_mesh_preserves_model_axis():
    m = elastic_mesh(1, model_parallel=1)
    assert m.devices.shape == (1, 1)
    with pytest.raises(RuntimeError):
        elastic_mesh(0, model_parallel=2)


def test_elastic_mesh_typed_errors_name_the_shortfall():
    """Both failure modes are typed with actionable messages: too few
    surviving devices for one TP group, and a survivor count that
    overstates what this process can actually see."""
    with pytest.raises(RuntimeError, match="cannot host"):
        elastic_mesh(1, model_parallel=2)
    # claims 16 survivors but only 1 CPU device is visible here
    with pytest.raises(RuntimeError, match="visible"):
        elastic_mesh(16, model_parallel=2)


def test_step_timer_flags_stragglers():
    t = StepTimer()
    for _ in range(20):
        t.observe(0.1)
    assert not t.is_straggler(0.11)
    assert t.is_straggler(0.5)


def test_deadline_skipper_bounded():
    t = StepTimer()
    for _ in range(20):
        t.observe(0.1)
    sk = DeadlineSkipper(deadline_factor=2.0, max_skips=2)
    assert sk.should_skip(1, waited_s=0.5, timer=t)
    assert sk.should_skip(2, waited_s=0.5, timer=t)
    assert not sk.should_skip(3, waited_s=0.5, timer=t)   # budget exhausted
    assert sk.skipped_steps == [1, 2]


def test_step_guard_recovers_from_failure(tmp_path):
    """Inject a failure mid-run; the guard restores and completes."""
    from repro.runtime.fault import StepGuard

    saves = {}

    def make_step(mesh):
        def step(state, batch):
            new = {"x": state["x"] + batch}
            saves[int(new["x"])] = new
            return new, {"x": new["x"]}
        return step

    def restore(mesh):
        best = max(saves)
        return saves[best], int(best)

    calls = {"n": 0}

    def injector(step):
        if step == 3 and calls["n"] == 0:
            calls["n"] += 1
            raise RuntimeError("simulated device failure")

    guard = StepGuard(make_step, restore, model_parallel=1)
    state, step, _ = guard.run({"x": jnp.asarray(0)},
                               batches=lambda s: jnp.asarray(1),
                               n_steps=6, fail_injector=injector)
    assert step == 6
    assert int(state["x"]) == 6
    assert len(guard.events) == 1


def test_step_guard_replay_is_deterministic(tmp_path):
    """Replay after restore must be bit-exact: the batch stream is a
    pure function of the step index, so a run that failed and replayed
    ends in the same state as one that never failed."""
    from repro.runtime.fault import StepGuard

    def run(inject):
        saves = {}

        def make_step(mesh):
            def step(state, batch):
                new = {"x": jnp.tanh(state["x"] * 0.9 + batch)}
                saves[len(saves)] = (new, None)
                return new, {}
            return step

        def restore(mesh):
            # restore from the checkpoint taken at step 2
            return ckpt[0], ckpt[1]

        ckpt = [None, 0]
        calls = {"n": 0}

        def stepper(s):
            return jnp.asarray(np.sin(s + 1), jnp.float32)

        def injector(step):
            if step == 2 and ckpt[0] is None:
                ckpt[0] = dict(state_box[0])
                ckpt[1] = step
            if inject and step == 4 and calls["n"] == 0:
                calls["n"] += 1
                raise RuntimeError("simulated failure")

        guard = StepGuard(make_step, restore, model_parallel=1)
        state_box = [{"x": jnp.asarray(0.5)}]

        def tracked_batches(s):
            b = stepper(s)
            return b

        # wrap step to keep a live view for the injector's checkpoint
        inner_make = guard.make_step

        def make_step_tracking(mesh):
            fn = inner_make(mesh)

            def step(state, batch):
                out, m = fn(state, batch)
                state_box[0] = out
                return out, m
            return step

        guard.make_step = make_step_tracking
        state, step, _ = guard.run(state_box[0], tracked_batches,
                                   n_steps=6, fail_injector=injector)
        assert step == 6
        assert len(guard.events) == (1 if inject else 0)
        return float(state["x"])

    assert run(inject=True) == run(inject=False)


# ---------------- compression collectives ----------------

def test_compressed_psum_single_axis():
    mesh = make_mesh((1,), ("data",))

    def f(x):
        y, err = compressed_psum(x, "data")
        return y, err

    x = jnp.linspace(-3, 3, 64)
    y, err = jax.shard_map(f, mesh=mesh, in_specs=P(None),
                           out_specs=(P(None), P(None)))(x)
    assert float(jnp.abs(y - x).max()) < 3 / 127 + 1e-6


def test_ef_compress_reduces_bias_over_steps():
    """Constant input: cumulative delivered ≈ cumulative true signal."""
    x = jnp.asarray(np.random.default_rng(0).standard_normal(512),
                    jnp.float32) * 0.01 + 1.7
    err = None
    total = jnp.zeros_like(x)
    for i in range(16):
        xh, err = ef_compress(x, err)
        total = total + xh
    rel = float(jnp.abs(total / 16 - x).max() / jnp.abs(x).max())
    assert rel < 0.005


# ---------------- hlo_stats trip-count correction ----------------

def test_hlo_stats_counts_loop_trips():
    from repro.analysis.hlo_stats import parse_hlo

    def f(w, x):
        def body(x, wl):
            return jnp.tanh(x @ wl), None
        x, _ = jax.lax.scan(body, x, w)
        return x

    w = jnp.zeros((6, 32, 32))
    x = jnp.zeros((8, 32))
    txt = jax.jit(f).lower(w, x).compile().as_text()
    stats = parse_hlo(txt, 1)
    expect = 6 * 2 * 8 * 32 * 32          # 6 scan iterations
    assert abs(stats["flops"] - expect) / expect < 0.05
