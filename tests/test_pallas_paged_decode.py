"""The Pallas paged decode on the model path (``decode_impl`` =
``pallas_paged``).

The plan picks it for a paged per-head K/V pool on one TPU chip; the
decode step then carries the stacked pools whole through the layer loop,
appends each layer's row in place and runs the kernel on layer ``i`` of
the stack.  Here the kernel runs in interpret mode and the XLA gather is
its oracle: the same logits, and the same rows appended in the same
places, tick after tick.
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ShapeConfig, get_arch
from repro.core.passes.lowering import build_run_cfg
from repro.core.pipeline import specialize
from repro.models import lm
from repro.models.lm import RunCfg
from repro.serve.engine import ServeEngine

XLA = RunCfg(block_q=16, ssd_chunk=16)
PALLAS = dataclasses.replace(XLA, decode_impl="pallas_paged")


def _tiny(name):
    arch = get_arch(name).reduced()
    if arch.window:
        # a window that bites inside the 64-position slots below
        arch = dataclasses.replace(arch, window=20)
    return arch


@pytest.mark.parametrize("name", ["qwen3-8b", "hymba-1.5b",
                                  "llama4-maverick-400b-a17b"])
def test_decode_step_pallas_paged_matches_xla(name):
    """GQA (qwen3), window layers beside an SSM (hymba) and two-layer
    super-blocks (llama4): over several ticks the Pallas path's logits
    match the XLA gather's within bf16 rounding.  The in-place appends
    write the rows the XLA path writes, where it writes them: the first
    layer's rows (same input) bitwise, a later layer's within the bf16
    rounding its input carries, and every row no slot appended to is
    left bitwise as it was."""
    arch = _tiny(name)
    params = lm.init_params(arch, jax.random.PRNGKey(5))
    B, max_len, bl, N = 4, 64, 16, 14
    cache = lm.init_paged_cache(arch, B, max_len, bl, N)
    for i, key in enumerate(lm.cache_rows(arch)):
        cache[key] = jax.random.normal(
            jax.random.PRNGKey(i), cache[key].shape).astype(cache[key].dtype)
    # a free slot (row -1, decoded at pos 0 as the engine does), a slot
    # one row short of a block boundary, a long one and a short one
    cache["block_tbl"] = jnp.asarray(
        [[0, 3, 7, -1], [-1] * 4, [5, 1, 9, 12], [2, -1, -1, -1]], jnp.int32)
    cache["pos"] = jnp.asarray([31, 0, 50, 3], jnp.int32)
    busy = np.asarray([0, 2, 3])
    run_x = jax.jit(lambda p, c, b: lm.decode_step(arch, p, c, b, XLA))
    run_p = jax.jit(lambda p, c, b: lm.decode_step(arch, p, c, b, PALLAS))
    cx, cp = dict(cache), dict(cache)
    tbl = np.asarray(cache["block_tbl"])
    written = np.zeros((N, bl), bool)
    tok = jnp.asarray([[3], [0], [5], [9]], jnp.int32)
    for _ in range(4):
        for b in busy:
            pos = int(cx["pos"][b])
            written[tbl[b, pos // bl], pos % bl] = True
        lx, cx = run_x(params, cx, {"tokens": tok})
        lp, cp = run_p(params, cp, {"tokens": tok})
        err = np.abs(np.asarray(lx, np.float32)[busy]
                     - np.asarray(lp, np.float32)[busy]).max()
        assert err < 2e-2, err
        for key in lm.cache_rows(arch):
            a = np.asarray(cx[key], np.float32)
            b = np.asarray(cp[key], np.float32)
            assert np.array_equal(a[0], b[0]), key
            assert np.array_equal(a[:, ~written], b[:, ~written]), key
            assert np.allclose(a, b, rtol=2e-2, atol=2e-2), key
        tok = jnp.argmax(lx[:, :arch.vocab_size], -1)[:, None] \
            .astype(jnp.int32)


def _one_chip_plan(arch, max_len):
    return specialize(arch, ShapeConfig("serve", "decode", max_len, 32),
                      mesh_axes=("data", "model"), mesh_shape=(1, 1),
                      cache=False)


def test_plan_picks_pallas_paged_for_per_head_pools_on_one_chip():
    """The benchmark's two configurations on one v5e: qwen3-8b's 16
    layers page per-head K/V and take the kernel; deepseek-v2-lite's
    latent rows keep the XLA decode; on a 2x4 mesh the pool is sharded
    and the plan records what it did before."""
    qwen = dataclasses.replace(get_arch("qwen3-8b"), n_layers=16)
    dsv2 = dataclasses.replace(get_arch("deepseek-v2-lite"),
                               n_routed_experts=8)
    plan = _one_chip_plan(qwen, 4096)
    assert plan.estimates["kv_residency"] == "paged"
    assert plan.estimates["decode_impl"] == "pallas_paged"
    plan = _one_chip_plan(dsv2, 6144)
    assert plan.estimates["kv_residency"] == "paged"
    assert plan.estimates.get("decode_impl", "xla") == "xla"
    assert build_run_cfg(plan, dsv2, None).decode_impl == "xla"
    wide = specialize(qwen, ShapeConfig("serve", "decode", 4096, 32),
                      mesh_axes=("data", "model"), mesh_shape=(2, 4),
                      cache=False)
    assert wide.estimates.get("decode_impl", "xla") != "pallas_paged"


def test_latent_decode_keeps_its_xla_program():
    """The latent cache shares nothing with the kernel: its lowered
    decode step is the same program whatever ``decode_impl`` says."""
    arch = get_arch("deepseek-v2-lite").reduced()
    params = lm.init_params(arch, jax.random.PRNGKey(0))
    cache = lm.init_paged_cache(arch, 4, 64, 16, 12)
    batch = {"tokens": jnp.zeros((4, 1), jnp.int32)}

    def text(cfg):
        return jax.jit(lambda p, c, b: lm.decode_step(arch, p, c, b, cfg)) \
            .lower(params, cache, batch).as_text()

    assert text(XLA) == text(PALLAS)


def test_from_plan_decodes_through_xla_off_a_tpu():
    """A plan that records ``pallas_paged`` runs the XLA gather on the
    CPU: the kernel compiles for a TPU only."""
    arch = get_arch("qwen3-8b").reduced()
    plan = specialize(arch, ShapeConfig("serve_t", "decode", 64, 4),
                      mesh_axes=("data", "model"), mesh_shape=(1, 1),
                      cache=False)
    assert plan.estimates["decode_impl"] == "pallas_paged"
    params = lm.init_params(arch, jax.random.PRNGKey(0),
                            *plan.padded_sizes())
    eng = ServeEngine.from_plan(plan, params, arch=arch)
    assert eng.decode_path == "xla"
    assert eng.telemetry()["decode_path"] == "xla"


def _serve(cfg, arch, params, seen):
    """Requests of mixed lengths, two behind a shared block; ``seen``
    collects each tick's (pos, block table) as the decode receives
    them."""
    rng = np.random.default_rng(4)
    V = arch.vocab_size
    eng = ServeEngine(arch, params, cfg, max_batch=4, max_len=64,
                      kv_residency="paged", kv_block_len=16, seed=2)
    decode = eng._decode

    def spy(p, c, b):
        # copies: the decode donates the cache, buffers and all
        seen.append((np.array(c["pos"]), np.array(c["block_tbl"])))
        return decode(p, c, b)

    eng._decode = spy
    system = rng.integers(0, V, 16).astype(np.int32)
    for n in (3, 14, 20, 9, 30):
        prompt = rng.integers(0, V, n).astype(np.int32)
        if n > 10:
            prompt = np.concatenate([system, prompt])
        eng.submit(prompt, max_new_tokens=int(rng.integers(4, 12)))
    eng.run_until_idle(max_ticks=200)
    return eng


def test_engine_counts_live_blocks_and_serves_the_same_tokens():
    """The engine's block counters add up over a short run: the live
    blocks are, tick by tick, ``ceil(len / block_len)`` over the busy
    slots (the row each appends included), never more than the table's
    entries; and the Pallas path serves the XLA path's tokens."""
    arch = get_arch("qwen3-8b").reduced()
    params = lm.init_params(arch, jax.random.PRNGKey(1))
    seen = []
    eng = _serve(XLA, arch, params, seen)
    st = eng.pressure_stats()
    bl = eng.block_len
    want = sum(int(np.sum(((pos + 1 + bl - 1) // bl)[tbl[:, 0] >= 0]))
               for pos, tbl in seen)
    assert st["decode_blocks_live"] == want > 0
    assert st["decode_blocks_table"] == sum(t.size for _, t in seen)
    assert st["decode_blocks_live"] <= st["decode_blocks_table"]
    json.dumps(eng.telemetry())
    pallas = _serve(PALLAS, arch, params, [])
    assert pallas.decode_path == "pallas_paged"
    assert ({r.rid: r.out_tokens for r in pallas.finished}
            == {r.rid: r.out_tokens for r in eng.finished})
    assert pallas.pressure_stats()["decode_blocks_live"] == want


def test_decode_span_carries_the_live_blocks(tmp_path):
    """Each ``serve.decode`` span says how many live blocks its tick's
    decode read; over a traced run they sum to the engine's counter."""
    import glob

    from jax.profiler import ProfileData
    arch = get_arch("qwen3-8b").reduced()
    params = lm.init_params(arch, jax.random.PRNGKey(1))
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng = _serve(XLA, arch, params, [])
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb")))
    live = [dict(ev.stats)["live_blocks"]
            for plane in ProfileData.from_file(path[-1]).planes
            for line in plane.lines for ev in line.events
            if ev.name == "serve.decode"]
    assert live and min(live) >= 1
    assert sum(live) == eng.pressure_stats()["decode_blocks_live"] > 0
