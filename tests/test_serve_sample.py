"""Batched sampling against the per-row ``_sample`` hook.

The engine samples every row of a tick in one ``serve_sample`` program;
an engine whose ``_sample`` is overridden draws row by row through the
hook.  The same schedule through both gives the same tokens, greedy and
sampled, in the decode ticks and in padded prefill groups of 1, 3 and 5
requests behind a shared (aliased) system prompt.
"""

import jax
import numpy as np
import pytest

from repro.configs import get_arch
from repro.models import lm
from repro.models.lm import RunCfg
from repro.serve.engine import ServeEngine

GROUPS = (1, 3, 5)


class PassThrough(ServeEngine):
    """Overrides the hook without changing it: takes the per-row path."""

    def _sample(self, r, logits, key):
        return super()._sample(r, logits, key)


@pytest.fixture(scope="module")
def model():
    arch = get_arch("qwen3-8b").reduced()
    return arch, lm.init_params(arch, jax.random.PRNGKey(0))


def _serve(cls, arch, params, temps):
    """Admit groups of 1, 3 and 5 same-length requests, one group a
    tick, each prompt opening with one shared block; ``temps`` cycles
    over the requests.  Returns the tokens by rid and the engine."""
    rng = np.random.default_rng(11)
    V = arch.vocab_size
    system = rng.integers(0, V, 16).astype(np.int32)
    eng = cls(arch, params, RunCfg(block_q=16, ssd_chunk=16),
              max_batch=16, max_len=48, kv_residency="paged",
              kv_block_len=16, kv_prefix_reuse="on", seed=3)
    n = 0
    for g, size in enumerate(GROUPS):
        for _ in range(size):
            user = rng.integers(0, V, 6 + 2 * g).astype(np.int32)
            eng.submit(np.concatenate([system, user]), max_new_tokens=7,
                       temperature=temps[n % len(temps)])
            n += 1
        eng.step()
    eng.run_until_idle(max_ticks=64)
    assert sorted(eng.prefill_batches) == sorted(GROUPS)
    return {r.rid: r.out_tokens for r in eng.finished}, eng


@pytest.mark.parametrize("temps", [(0.0,), (0.7,), (0.0, 0.7)],
                         ids=["greedy", "sampled", "mixed"])
def test_batched_sampling_matches_per_row_hook(model, temps):
    arch, params = model
    batched, eng = _serve(ServeEngine, arch, params, temps)
    per_row, probe = _serve(PassThrough, arch, params, temps)
    assert len(batched) == sum(GROUPS)
    assert all(len(t) == 7 for t in batched.values())
    assert batched == per_row
    tokens = sum(len(t) for t in batched.values())
    ps, pp = eng.pressure_stats(), probe.pressure_stats()
    assert ps["prefix_hits"] >= sum(GROUPS) - 1
    assert (ps["sampled_batched"], ps["sampled_per_slot"]) == (tokens, 0)
    assert (pp["sampled_batched"], pp["sampled_per_slot"]) == (0, tokens)


def test_a_patched_hook_on_the_class_or_instance_is_called(model,
                                                          monkeypatch):
    arch, params = model
    calls = []
    orig = ServeEngine._sample

    def counting(self, r, logits, key):
        calls.append(r.rid)
        return orig(self, r, logits, key)

    monkeypatch.setattr(ServeEngine, "_sample", counting)
    on_class, _ = _serve(ServeEngine, arch, params, (0.0, 0.7))
    assert len(calls) == sum(len(t) for t in on_class.values())
    monkeypatch.undo()
    eng = ServeEngine(arch, params, RunCfg(block_q=16, ssd_chunk=16),
                      max_batch=2, max_len=32)
    eng._sample = counting.__get__(eng)
    calls.clear()
    eng.submit(np.arange(5, dtype=np.int32), max_new_tokens=3)
    eng.run_until_idle(max_ticks=16)
    assert len(calls) == 3
    assert eng.pressure_stats()["sampled_per_slot"] == 3
