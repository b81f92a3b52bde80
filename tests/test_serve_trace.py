"""The serving engine's spans and program names in a profiler trace.

A tiny paged engine with prefix reuse and a host tier runs a schedule
that takes every engine path the spans mark: prefill and tail prefill,
a prefix hit on a block the pool's pressure spilled to the host tier
(spill inside an admission, promote inside the next), a copy-on-write,
decode and sampling.  Traced, every ``serve.*`` span appears inside a
``serve.step``, the programs carry their ``jit_serve_*`` names (sampling
is one ``jit_serve_sample`` program, and its span says so), and the
served tokens equal those of the same schedule untraced.
"""

import glob

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_arch
from repro.models import lm
from repro.models.lm import RunCfg
from repro.serve.engine import ServeEngine

SPANS = {"serve.step", "serve.readmit", "serve.admit", "serve.grant",
         "serve.prefill", "serve.decode", "serve.sample", "serve.spill",
         "serve.promote", "serve.cow"}


def _schedule(arch, params):
    rng = np.random.default_rng(5)
    V = arch.vocab_size
    sys_p = rng.integers(0, V, 16).astype(np.int32)   # one block

    def prompt(n, shared):
        t = rng.integers(0, V, n).astype(np.int32)
        return np.concatenate([sys_p, t]) if shared else t

    p1, p2 = prompt(4, True), prompt(6, True)
    p3, p4 = prompt(20, False), prompt(20, False)
    # 4 pool blocks of 16: p1 leaves its system block cached; p3 and p4
    # need all four, so admitting p4 spills it to the host tier; p2's
    # hit then promotes it back and prefills only its tail
    eng = ServeEngine(arch, params, RunCfg(block_q=16, ssd_chunk=16),
                      max_batch=2, max_len=32, kv_residency="paged",
                      kv_block_len=16, kv_prefix_reuse="on",
                      kv_host_blocks=8)
    assert eng.n_blocks == 4
    eng.submit(p1, max_new_tokens=6)
    eng.run_until_idle(max_ticks=64)
    eng.submit(p3, max_new_tokens=6)
    eng.submit(p4, max_new_tokens=6)
    eng.run_until_idle(max_ticks=64)
    eng.submit(p2, max_new_tokens=6)
    eng.step()
    # a sharer appears on p2's append block: the next tick copies it
    r = next(iter(eng.active.values()))
    blk = r.blocks[int(eng.slot_len[r.slot]) // eng.block_len]
    eng._alloc.retain([blk])
    eng.step()
    eng._release_blocks([blk])
    eng.run_until_idle(max_ticks=64)
    ps = eng.pressure_stats()
    assert ps["spills"] >= 1 and ps["promotes"] >= 1
    assert ps["prefix_hits"] >= 1 and ps["cow_copies"] == 1
    return {r.rid: r.out_tokens for r in eng.finished}


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    arch = get_arch("qwen3-8b").reduced()
    params = lm.init_params(arch, jax.random.PRNGKey(0))
    plain = _schedule(arch, params)
    d = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(d))
    try:
        served = _schedule(arch, params)
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(str(d / "plugins/profile/*/*.xplane.pb")))[-1]
    spans, modules, sample_args = [], set(), []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serve."):
                    s = ev.start_ns
                    spans.append((ev.name, s, s + ev.duration_ns))
                if ev.name == "serve.sample":
                    sample_args.append(dict(ev.stats))
                modules.update(v for k, v in ev.stats if k == "hlo_module")
    return plain, served, spans, modules, sample_args


def test_every_engine_span_is_traced(traced):
    _, _, spans, _, _ = traced
    assert {n for n, _, _ in spans} == SPANS


def test_spans_nest_in_a_tick(traced):
    _, _, spans, _, _ = traced
    ticks = [(s, e) for n, s, e in spans if n == "serve.step"]
    for n, s, e in spans:
        if n != "serve.step":
            assert any(a <= s and e <= b for a, b in ticks), n


def test_programs_carry_stable_names(traced):
    _, _, _, modules, _ = traced
    assert {"jit_serve_decode", "jit_serve_sample"} <= modules
    assert {"jit_serve_prefill", "jit_serve_prefill_tail"} <= modules
    assert {"jit_serve_cow", "jit_serve_gather_blocks",
            "jit_serve_scatter_blocks"} <= modules
    assert not any("lambda" in m for m in modules)


def test_tracing_leaves_served_tokens_unchanged(traced):
    plain, served, _, _, _ = traced
    assert served == plain and len(served) == 4


def test_sample_span_says_the_rows_were_batched(traced):
    _, _, _, _, sample_args = traced
    assert sample_args and all(a.get("batched") == 1 for a in sample_args)
