"""Disaggregated prefill/decode: chunked block-native prefill identity,
plan-hash handshake, worker-kill journal resume, degraded fallback.

The load-bearing invariant everything here leans on: iterating
``lm.prefill_tail`` over block-sized slices (``lm.prefill_chunked``)
produces KV rows and last-token logits **bitwise identical** to the
dense one-shot ``lm.prefill``.  That identity is what makes the chunk
journal idempotent (a re-sent chunk overwrites equal bytes), the
resume token-exact (journaled rows ARE the prefix KV), and the
degraded inline fallback divergence-free.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ShapeConfig, get_arch
from repro.core.pipeline import specialize
from repro.models import lm
from repro.models.lm import RunCfg
from repro.serve import PlanHandshakeError, PrefillFleet, ServeEngine


@pytest.fixture(scope="module")
def setup():
    arch = get_arch("qwen3-8b").reduced()
    shape = ShapeConfig("serve_disagg_t", "decode", 64, 2)
    plan = specialize(arch, shape, mesh_axes=("data", "model"),
                      mesh_shape=(1, 1))
    assert plan.estimates.get("kv_residency") == "paged"
    params = lm.init_params(arch, jax.random.PRNGKey(0),
                            *plan.padded_sizes())
    return arch, plan, params


OPTS = {"heartbeat_s": 0.2, "backoff_base_s": 0.05,
        "backoff_cap_s": 0.2, "chunk_delay_s": 0.05}


def test_chunked_prefill_bitwise_identical_to_dense():
    """Chunked == dense, bitwise, across block-aligned and ragged
    prompt lengths — the cornerstone the disagg path stands on."""
    arch = get_arch("qwen3-8b").reduced()
    cfg = RunCfg(block_q=16, ssd_chunk=16)
    params = lm.init_params(arch, jax.random.PRNGKey(5))
    bl = 16
    for plen in (17, 48, 49):
        p = (np.arange(plen, dtype=np.int32) * 7 + 3) % arch.vocab_size
        lg_full, cache = lm.prefill(
            arch, params, {"tokens": jnp.asarray(p[None])}, cfg,
            max_len=64)
        chunks = []
        lg_c, rows = lm.prefill_chunked(
            arch, params, p, bl, cfg, kv_heads=cache["k"].shape[3],
            on_chunk=lambda i, r: chunks.append(i))
        k_c = np.asarray(jnp.concatenate([r["k"] for r in rows], axis=1))
        v_c = np.asarray(jnp.concatenate([r["v"] for r in rows], axis=1))
        assert chunks == list(range(-(-plen // bl)))
        assert (np.asarray(cache["k"][:, 0, :plen]) == k_c).all()
        assert (np.asarray(cache["v"][:, 0, :plen]) == v_c).all()
        assert (np.asarray(lg_full[0]) == np.asarray(lg_c)).all()


def test_chunked_prefill_rejects_bad_inputs():
    arch = get_arch("qwen3-8b").reduced()
    cfg = RunCfg(block_q=16, ssd_chunk=16)
    params = lm.init_params(arch, jax.random.PRNGKey(5))
    tok = np.arange(8, dtype=np.int32)
    with pytest.raises(ValueError):
        lm.prefill_chunked(arch, params, tok, 0, cfg)
    with pytest.raises(ValueError):
        lm.prefill_chunked(arch, params, np.zeros((0,), np.int32), 16, cfg)
    ssm = get_arch("mamba2-2.7b").reduced()
    with pytest.raises(ValueError):
        lm.prefill_chunked(ssm, lm.init_params(ssm, jax.random.PRNGKey(0)),
                           tok, 16, cfg)


def test_handshake_rejects_mismatched_plan_hash(setup):
    """A worker whose rebuilt plan hashes differently must be refused
    before any KV crosses the wire — typed, not a crash or a silent
    geometry mismatch."""
    arch, plan, params = setup
    with pytest.raises(PlanHandshakeError, match="content hash"):
        PrefillFleet(plan, arch, params, 1, block_len=16,
                     _expect_hash="0" * 64, **OPTS)


def test_kill_mid_prefill_resumes_from_journal(setup):
    """SIGKILL the worker with a prefill half-journaled: the engine
    must re-dispatch from the last acked block boundary and finish
    token-identical to the inline oracle, with the pool whole."""
    import time
    arch, plan, params = setup
    p = (np.arange(49, dtype=np.int32) * 7 + 3) % arch.vocab_size

    ref = ServeEngine.from_plan(plan, params, arch=arch, max_batch=1)
    ref.submit(p, max_new_tokens=6)
    want = list(ref.run_until_idle(256)[0].out_tokens)

    eng = ServeEngine.from_plan(
        plan, params, arch=arch, seed=0, kv_prefill_mode="disagg",
        disagg_workers=1, disagg_opts=dict(OPTS))
    assert eng.prefill_mode == "disagg"
    rid = eng.submit(p, max_new_tokens=6)
    killed = False
    deadline = time.time() + 420
    while (eng.pending or eng.active or eng._disagg) \
            and time.time() < deadline:
        eng.step()
        fl = eng._disagg.get(rid)
        if not killed and fl is not None and 1 <= fl.acked < fl.nb_feed:
            killed = eng._fleet.kill_worker(rid=rid)
    assert killed, "kill window never opened mid-prefill"
    [r] = [q for q in eng.finished if q.rid == rid]
    assert list(r.out_tokens) == want, "TOKEN DIVERGENCE after kill"
    tel = eng.telemetry()
    json.dumps(tel)                  # the snapshot serializes whole
    assert tel["prefill"]["disagg"]["fleet"]["deaths"] >= 1
    assert tel["prefill"]["disagg"]["resumes"] >= 1
    st = eng.block_stats()
    assert st["in_use"] == st["cached"], f"blocks leaked: {st}"
    eng.shutdown()


def test_restart_budget_exhaustion_degrades_to_inline(setup):
    """Kill the only worker under ``max_restarts=0``: the fleet
    retires, the engine flips to a typed DegradedMode, and the orphaned
    request completes in-process with identical tokens — never a
    crash."""
    import time
    arch, plan, params = setup
    p = (np.arange(33, dtype=np.int32) * 11 + 5) % arch.vocab_size

    ref = ServeEngine.from_plan(plan, params, arch=arch, max_batch=1)
    ref.submit(p, max_new_tokens=6)
    want = list(ref.run_until_idle(256)[0].out_tokens)

    eng = ServeEngine.from_plan(
        plan, params, arch=arch, seed=0, kv_prefill_mode="disagg",
        disagg_workers=1, disagg_opts=dict(OPTS, max_restarts=0))
    rid = eng.submit(p, max_new_tokens=6)
    killed = False
    deadline = time.time() + 420
    while (eng.pending or eng.active or eng._disagg) \
            and time.time() < deadline:
        eng.step()
        fl = eng._disagg.get(rid)
        if not killed and fl is not None and fl.acked >= 1:
            killed = eng._fleet.kill_worker(rid=rid)
    assert killed
    [r] = [q for q in eng.finished if q.rid == rid]
    assert list(r.out_tokens) == want, "TOKEN DIVERGENCE in fallback"
    assert eng.prefill_mode == "degraded"
    assert eng.degraded is not None and eng.degraded.worker_deaths >= 1
    press = eng.pressure_stats()
    assert press["degraded"]["reason"].startswith("all 1 prefill worker")
    st = eng.block_stats()
    assert st["in_use"] == st["cached"], f"blocks leaked: {st}"
    eng.shutdown()


def test_from_plan_inline_without_workers(setup):
    """disagg mode with zero workers quietly keeps the inline path —
    the same fallback the pass itself takes for SSM archs."""
    arch, plan, params = setup
    eng = ServeEngine.from_plan(plan, params, arch=arch,
                                kv_prefill_mode="disagg")
    assert eng.prefill_mode == "inline"
    p = (np.arange(17, dtype=np.int32) * 3 + 1) % arch.vocab_size
    eng.submit(p, max_new_tokens=4)
    [r] = eng.run_until_idle(256)
    assert len(r.out_tokens) == 4
    json.dumps(eng.telemetry())     # fleet=None branch serializes too


def test_plan_records_prefill_mode(setup):
    """The data-organization pass records the interference verdict in
    the plan estimates; the full-size 32k deployment flips to disagg
    while the reduced test plan stays inline."""
    _, plan, _ = setup
    est = plan.estimates
    assert est.get("kv_prefill_mode") == "inline"
    assert est.get("kv_prefill_chunk", 0) >= 1
    full = specialize("qwen3-8b", "decode_32k")
    assert full.estimates.get("kv_prefill_mode") == "disagg"
    assert full.estimates.get("kv_prefill_stall_ticks", 0.0) > 8.0
