"""Latent attention (MLA) with a latent paged cache, and the held-expert
dropless MoE layer, at small widths on the CPU (deepseek-v2-lite's
shape: a leading dense layer, then MoE layers; 4 of 8 experts held).

The program is compared with the benchmark's float32 reference
(``bench/reference/deepseek_v2.py``, reached through the ``ref``
fixture) on weights both draw from one seed (``bench/weights.py``)."""

import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ShapeConfig, get_arch
from repro.core.pipeline import specialize
from repro.models import lm
from repro.models.lm import RunCfg
from repro.models.moe import MoEParams, _capacity, moe_dense_einsum
from repro.serve.engine import ServeEngine

BENCH = Path(__file__).resolve().parents[1] / "bench"
ARCH = dataclasses.replace(get_arch("deepseek-v2-lite").reduced(),
                           n_routed_experts=4)
CFG = RunCfg(block_q=16, moe_impl="held_dropless")
SEED = 2**31 + 13
# bf16 weights are shared with the reference; bf16 activations, latent
# rows and logits are not, nor the absorbed decode's order of products
REF_TOL = 0.02


@pytest.fixture(scope="module")
def ref():
    """The benchmark's reference modules, imported from ``bench/``."""
    if str(BENCH) not in sys.path:
        sys.path.insert(0, str(BENCH))
    import weights
    from reference import deepseek_v2
    return deepseek_v2, weights


@pytest.fixture(scope="module")
def params(ref):
    _, weights = ref
    shapes = jax.eval_shape(lambda k: lm.init_params(ARCH, k),
                            jax.random.PRNGKey(0))
    return weights.make(shapes, SEED, ARCH.n_layers)


class Probe(ServeEngine):
    """Records every sampled logits row (fp32, vocab slice) per rid."""

    def _sample(self, r, logits, key):
        self.__dict__.setdefault("rows", {}).setdefault(r.rid, []).append(
            np.asarray(logits[:self.arch.vocab_size].astype(jnp.float32)))
        return super()._sample(r, logits, key)


def _engine(params, cls=ServeEngine, **kw):
    kw = {"max_batch": 2, "max_len": 64, "kv_residency": "paged",
          "kv_block_len": 16, **kw}
    return cls(ARCH, params, CFG, **kw)


def _prompts(seed, lens, doc=0):
    rng = np.random.default_rng(seed)
    d = rng.integers(0, ARCH.vocab_size, doc, dtype=np.int32)
    return [np.concatenate([d, rng.integers(0, ARCH.vocab_size, n,
                                            dtype=np.int32)]) for n in lens]


@pytest.mark.parametrize("residency", ["dense", "paged"])
def test_prefill_then_decode_matches_reference(ref, params, residency):
    deepseek_v2, _ = ref
    eng = _engine(params, Probe, kv_residency=residency)
    assert eng._pools == ("ckv",)
    prompts = _prompts(1, (21, 35))
    rids = [eng.submit(p, max_new_tokens=7) for p in prompts]
    done = {r.rid: r for r in eng.run_until_idle(max_ticks=64)}
    seqs, at = [], []
    for rid, p in zip(rids, prompts):
        out = done[rid].out_tokens
        seqs.append(np.concatenate([p, np.asarray(out[:-1], np.int32)]))
        at.append(np.arange(len(p) - 1, len(p) - 1 + len(out)))
    want = deepseek_v2.served_logits(dataclasses.asdict(ARCH), SEED, seqs,
                                     at, pad_to=16)
    for rid, r in zip(rids, want):
        got = np.stack(eng.rows[rid])
        assert np.abs(got - r).max() <= REF_TOL * np.abs(r).max(), rid
    held = eng.pressure_stats()["moe_held_pairs"]
    assert held["prefill"] > 0 and held["decode"] > 0


def test_prefix_hit_tail_prefill_matches_full_prefill(params):
    """A request whose 32-token document is resident prefills only its
    tail over the aliased latent blocks; its tokens equal those of an
    engine that prefills the whole prompt.  The logits agree to bf16
    rounding: the tail prefill multiplies fewer rows, and the CPU's
    matrix products may round a row differently with the row count."""
    p1, p2 = _prompts(2, (5, 9), doc=32)

    def run(reuse):
        eng = _engine(params, Probe, kv_prefix_reuse=reuse)
        eng.submit(p1, max_new_tokens=6)
        eng.step()
        eng.submit(p2, max_new_tokens=6)
        done = eng.run_until_idle(max_ticks=64)
        return {r.rid: r.out_tokens for r in done}, eng

    want, full = run("off")
    got, tail = run("on")
    assert tail.pressure_stats()["prefix_hit_tokens"] == 32
    assert got == want
    a, b = np.stack(tail.rows[1]), np.stack(full.rows[1])
    assert np.abs(a - b).max() <= 0.01 * np.abs(b).max()


def test_latent_blocks_spill_promote_and_cow_bit_identical(params):
    p1, p2 = _prompts(3, (4, 6), doc=16)
    eng = _engine(params, kv_prefix_reuse="on", kv_host_blocks=8)
    eng.submit(p1, max_new_tokens=4)
    eng.run_until_idle(max_ticks=32)
    (blk,) = [b for b in eng._cached if b < eng.n_blocks]
    rows = np.asarray(eng.cache["ckv"][:, blk])
    assert eng.spill_cached() == 1
    (host,) = list(eng._cached)
    assert (eng._host["ckv"][:, host - eng.n_blocks] == rows).all()
    eng.submit(p2, max_new_tokens=4)      # the document's hit promotes it
    eng.step()
    r = next(iter(eng.active.values()))
    assert eng.pressure_stats()["promotes"] == 1
    assert (np.asarray(eng.cache["ckv"][:, r.blocks[0]]) == rows).all()
    # a sharer appears on the append block: the write barrier copies it
    col = int(eng.slot_len[r.slot]) // eng.block_len
    old = r.blocks[col]
    eng._alloc.retain([old])
    eng._ensure_writable()
    assert eng.cow_copies == 1 and r.blocks[col] != old
    assert (np.asarray(eng.cache["ckv"][:, r.blocks[col]])
            == np.asarray(eng.cache["ckv"][:, old])).all()
    eng._release_blocks([old])
    eng.run_until_idle(max_ticks=32)
    eng.drop_block_cache()
    st = eng.block_stats()
    assert st["free"] == st["total"] and st["host_free"] == st["host_total"]


def _moe_params(key, E, Eh, d=32, ff=16, shared=2, bias=0.0):
    k = jax.random.split(key, 5)
    router = jax.random.normal(k[0], (d, E)) * 0.5
    router = router.at[:, 0].add(bias)
    return MoEParams(router, jax.random.normal(k[1], (Eh, d, 2 * ff)) * 0.3,
                     jax.random.normal(k[2], (Eh, ff, d)) * 0.3,
                     jax.random.normal(k[3], (d, 2 * ff * shared)) * 0.3,
                     jax.random.normal(k[4], (ff * shared, d)) * 0.3)


def _reference_layer(ref, x, p, top_k):
    deepseek_v2, _ = ref
    cfg = {"experts_per_token": top_k}
    leaves = {"moe/router": p.router, "moe/wi": p.wi, "moe/wo": p.wo,
              "moe/shared_wi": p.shared_wi, "moe/shared_wo": p.shared_wo}
    with jax.default_matmul_precision("highest"):
        return deepseek_v2.moe(x, leaves, cfg, "f32")


def test_expert_shares_add_up_to_the_uncut_layer(ref):
    """Eight chips' shares of a 16-expert layer: each computes its two
    experts' part of the routed sum; the shared experts, which every
    chip computes alike, are counted once.  A share holds the router's
    first experts, so share ``s`` sees the router's columns rotated to
    put its experts ``2s, 2s + 1`` first."""
    E, k = 16, 3
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 9, 32))
    p = _moe_params(jax.random.PRNGKey(2), E, E)
    def share(s):
        shared = (p.shared_wi, p.shared_wo) if s == 0 else (None, None)
        return MoEParams(jnp.roll(p.router, -2 * s, axis=1),
                         p.wi[2 * s:2 * s + 2], p.wo[2 * s:2 * s + 2],
                         *shared)

    with jax.default_matmul_precision("highest"):
        total = sum(moe_dense_einsum(x, share(s), top_k=k)[0]
                    for s in range(8))
    want = _reference_layer(ref, x, p, k)
    assert np.allclose(total, want, atol=1e-4, rtol=1e-4)


def test_no_token_is_dropped_past_any_capacity(ref):
    """Every token's top choice is held expert 0: 40 tokens on one
    expert, where a capacity factor of 1.25 would keep 8."""
    E, Eh, k, T = 8, 4, 2, 40
    x = jax.random.normal(jax.random.PRNGKey(3), (1, T, 32))
    p = _moe_params(jax.random.PRNGKey(4), E, Eh, bias=30.0)
    assert _capacity(T, E, k, 1.25) < T
    with jax.default_matmul_precision("highest"):
        y, _, held = moe_dense_einsum(x, p, top_k=k)
    assert int(held.sum()) >= T
    want = _reference_layer(ref, x, p, k)
    assert np.allclose(y, want, atol=1e-4, rtol=1e-4)


def test_plan_sizes_the_latent_pool_from_its_bytes_per_token():
    """One chip's share of deepseek-v2-lite: the pool is sized from the
    latent row (27 layers x 576 values x 2 B = 31,104 B a token), not
    from per-head K/V, which would cost 8.9 times as much."""
    arch = dataclasses.replace(get_arch("deepseek-v2-lite"),
                               n_routed_experts=8)
    assert arch.cache_bytes_per_token() == 31104
    plan = specialize(arch, ShapeConfig("serve", "decode", 6144, 32),
                      mesh_axes=("data", "model"), mesh_shape=(1, 1),
                      cache=False)
    e = plan.estimates
    n, bl = int(e["kv_n_blocks"]), int(e["kv_block_len"])
    assert e["kv_paged_bytes"] == n * bl * 31104
    left = e["hbm_budget_bytes"] - e["persistent_bytes_per_dev"]
    assert n == int(left // (bl * 31104))
    per_head = 27 * 16 * (192 + 128) * 2
    assert per_head / 31104 > 8.8
    assert n > 8 * int(left // (bl * per_head))
