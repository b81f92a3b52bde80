"""Multi-device SPMD tests (subprocess with 8 host devices).

The main test process must keep the single real CPU device (smoke tests),
so anything needing a real mesh runs in a subprocess with
``--xla_force_host_platform_device_count=8``.
"""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

SRC = str(Path(__file__).resolve().parents[1] / "src")


def run_subprocess(code: str, timeout: int = 300) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    # force the host platform: the device-count flag only applies to it,
    # and autodetection in the child probes for a Cloud TPU (30 slow
    # metadata retries) on machines with libtpu installed but no TPU
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-c",
         "from repro.launch.mesh import make_mesh\n" + textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_flash_decode_sharded_matches_oracle():
    run_subprocess("""
        import jax, jax.numpy as jnp
        from repro.dist.flash_decode import flash_decode
        from repro.kernels import ref
        mesh = make_mesh((2, 4), ("data", "model"))
        B, S, H, K, D = 4, 64, 8, 4, 16
        ks = jax.random.split(jax.random.PRNGKey(0), 5)
        q = jax.random.normal(ks[0], (B, 1, H, D))
        kn = jax.random.normal(ks[1], (B, 1, K, D))
        vn = jax.random.normal(ks[2], (B, 1, K, D))
        kc = jax.random.normal(ks[3], (B, S, K, D))
        vc = jax.random.normal(ks[4], (B, S, K, D))
        for pos, win in ((10, 0), (40, 16), (63, 0)):
            ctx, kc2, vc2 = jax.jit(
                lambda *a: flash_decode(*a, mesh=mesh))(
                    q, kn, vn, kc, vc, pos, win)
            kr = kc.at[:, pos].set(kn[:, 0])
            vr = vc.at[:, pos].set(vn[:, 0])
            r = ref.decode_attention_ref(q[:, 0], kr, vr,
                                         cache_len=jnp.int32(pos + 1),
                                         window=win)
            err = float(jnp.abs(ctx[:, 0] - r).max())
            assert err < 1e-5, (pos, win, err)
            assert bool(jnp.allclose(kc2, kr)), "append corrupted cache"
        # per-slot (B,) positions: mixed batch fill, appends cross shard
        # boundaries (local seq slice is 16 wide) and masks stay exact
        for pos_list, win in (([10, 40, 63, 0], 0), ([5, 17, 33, 60], 16)):
            pos = jnp.asarray(pos_list, jnp.int32)
            ctx, kc2, vc2 = jax.jit(
                lambda *a: flash_decode(*a, mesh=mesh))(
                    q, kn, vn, kc, vc, pos, win)
            kr = ref.decode_append_ref(kc, kn, pos)
            vr = ref.decode_append_ref(vc, vn, pos)
            r = ref.decode_attention_ref(q[:, 0], kr, vr,
                                         cache_len=pos + 1, window=win)
            err = float(jnp.abs(ctx[:, 0] - r).max())
            assert err < 1e-5, (pos_list, win, err)
            assert bool(jnp.allclose(kc2, kr)), "per-slot append corrupted"
        print("OK")
    """)


def test_serve_from_plan_shard_map_flash_end_to_end():
    """ServeEngine.from_plan(mesh=...) drives the plan's seq-sharded
    shard_map flash-decode path on a real 8-wide model axis, and a mixed
    continuous batch is token-identical to sequential single-request
    serving through the same path (cross-impl token equality is NOT
    asserted: flash's online-softmax combine and XLA's dense softmax
    differ in rounding, which can flip a near-tie greedy argmax)."""
    run_subprocess("""
        import dataclasses, jax, numpy as np
        from repro.configs import ShapeConfig, get_arch
        from repro.core.pipeline import specialize
        from repro.models import lm
        from repro.serve.engine import ServeEngine

        # GQA-on-wide-TP: kv=1 not shardable by model=8 -> seq spill
        arch = dataclasses.replace(get_arch("qwen3-8b").reduced(),
                                   n_kv_heads=1)
        shape = ShapeConfig("serve_md", "decode", 32, 2)
        # this test pins the DENSE seq-sharded path; the paged pool-
        # sharded run is test_serve_from_plan_paged_pool_sharded
        plan = specialize(arch, shape, mesh_axes=("data", "model"),
                          mesh_shape=(1, 8), cache=False,
                          kv_residency="dense")
        assert plan.estimates.get("decode_impl") == "shard_map_flash"
        assert plan.estimates.get("kv_residency") == "dense"
        mesh = make_mesh((1, 8), ("data", "model"))
        params = lm.init_params(arch, jax.random.PRNGKey(0),
                                *plan.padded_sizes())
        eng = ServeEngine.from_plan(plan, params, arch=arch, mesh=mesh)
        assert eng.decode_path == "shard_map_flash", eng.decode_path
        # KV cache really lands seq-sharded on the model axis
        kshard = eng.cache["k"].sharding.spec
        assert kshard[2] == "model", kshard
        prompts = [np.arange(5, dtype=np.int32) % arch.vocab_size,
                   (np.arange(11, dtype=np.int32) * 3) % arch.vocab_size,
                   (np.arange(8, dtype=np.int32) * 7) % arch.vocab_size]
        for p in prompts:
            eng.submit(p, max_new_tokens=5)
        done = eng.run_until_idle(max_ticks=64)
        assert len(done) == 3 and all(len(r.out_tokens) == 5 for r in done)
        # sequential single-request oracles through the SAME sharded path
        a = {r.prompt.tobytes(): r.out_tokens for r in done}
        for p in prompts:
            eng2 = ServeEngine.from_plan(plan, params, arch=arch,
                                         mesh=mesh, max_batch=1)
            assert eng2.decode_path == "shard_map_flash"
            eng2.submit(p, max_new_tokens=5)
            done2 = eng2.run_until_idle(max_ticks=32)
            assert a[p.tobytes()] == done2[0].out_tokens, (
                p, a[p.tobytes()], done2[0].out_tokens)
        print("OK")
    """, timeout=600)


def test_flash_decode_paged_pool_sharded_matches_oracle():
    """The 1-D paged combine over a pool sharded on the model axis:
    owning-shard appends + per-shard partial softmax over owned blocks
    == the gather oracle, for staggered tables with unassigned tails.
    B=3 on data=2 cannot partition the batch, so the pool replicates
    over the data axis and every data shard must append the FULL batch
    or the replicas diverge — regression for the batch-sharded-append
    bug (the partitioned-batch run is the 2-D test below)."""
    run_subprocess("""
        import jax, jax.numpy as jnp
        from repro.dist.flash_decode import flash_decode_paged, \\
            pool_sharding_kind
        from repro.kernels import ref
        mesh = make_mesh((2, 4), ("data", "model"))
        B, H, K, D, bl, N = 3, 8, 4, 16, 8, 16       # 4 blocks per shard
        assert pool_sharding_kind(mesh, N, B) == "1d"
        ks = jax.random.split(jax.random.PRNGKey(0), 5)
        q = jax.random.normal(ks[0], (B, 1, H, D))
        kn = jax.random.normal(ks[1], (B, 1, K, D))
        vn = jax.random.normal(ks[2], (B, 1, K, D))
        kp = jax.random.normal(ks[3], (N, bl, K, D))
        vp = jax.random.normal(ks[4], (N, bl, K, D))
        tbl = jnp.asarray([[0, 9, 3, -1], [14, 2, -1, -1],
                           [5, 7, 11, 13]], jnp.int32)
        for pos_list, win in (([16, 8, 31], 0), ([20, 14, 27], 8)):
            pos = jnp.asarray(pos_list, jnp.int32)
            ctx, kp2, vp2 = jax.jit(
                lambda *a: flash_decode_paged(*a, mesh=mesh))(
                    q, kn, vn, kp, vp, tbl, pos, win)
            kr = ref.paged_append_ref(kp, kn, pos, tbl)
            vr = ref.paged_append_ref(vp, vn, pos, tbl)
            r = ref.paged_decode_attention_ref(
                q[:, 0], kr, vr, tbl, cache_len=pos + 1, window=win)
            err = float(jnp.abs(ctx[:, 0] - r).max())
            assert err < 1e-5, (pos_list, win, err)
            assert bool(jnp.allclose(kp2, kr)), "paged append corrupted"
            assert bool(jnp.allclose(vp2, vr))
        print("OK")
    """)


def test_flash_decode_paged_2d_matches_oracle():
    """The 2-D paged combine on a 2x4 data×model mesh: the block dim is
    sharded data-major over both axes, batch slots are partitioned (not
    replicated) across data, appends land on the one (data, model)
    shard owning the block, and the model-axis-only 3-term combine ==
    the gather oracle — for staggered tables (each slot's blocks inside
    its data shard's sub-pool, the allocator contract) with unassigned
    tails and windows."""
    run_subprocess("""
        import jax, jax.numpy as jnp
        from repro.dist.flash_decode import flash_decode_paged, \\
            pool_sharding_kind
        from repro.kernels import ref
        mesh = make_mesh((2, 4), ("data", "model"))
        B, H, K, D, bl, N = 4, 8, 4, 16, 8, 16   # 2 blocks/(data,model) shard
        assert pool_sharding_kind(mesh, N, B) == "2d"
        ks = jax.random.split(jax.random.PRNGKey(1), 5)
        q = jax.random.normal(ks[0], (B, 1, H, D))
        kn = jax.random.normal(ks[1], (B, 1, K, D))
        vn = jax.random.normal(ks[2], (B, 1, K, D))
        kp = jax.random.normal(ks[3], (N, bl, K, D))
        vp = jax.random.normal(ks[4], (N, bl, K, D))
        # slots 0-1 live on data shard 0 (sub-pool ids [0, 8)), slots
        # 2-3 on data shard 1 (ids [8, 16)); non-contiguous, unordered
        tbl = jnp.asarray([[0, 5, 3, -1], [7, 2, -1, -1],
                           [8, 15, 11, 13], [9, 14, -1, -1]], jnp.int32)
        for pos_list, win in (([16, 8, 31, 10], 0), ([20, 14, 27, 4], 8),
                              ([0, 15, 24, 9], 6)):
            pos = jnp.asarray(pos_list, jnp.int32)
            ctx, kp2, vp2 = jax.jit(
                lambda *a: flash_decode_paged(*a, mesh=mesh))(
                    q, kn, vn, kp, vp, tbl, pos, win)
            kr = ref.paged_append_ref(kp, kn, pos, tbl)
            vr = ref.paged_append_ref(vp, vn, pos, tbl)
            r = ref.paged_decode_attention_ref(
                q[:, 0], kr, vr, tbl, cache_len=pos + 1, window=win)
            err = float(jnp.abs(ctx[:, 0] - r).max())
            assert err < 1e-5, (pos_list, win, err)
            assert bool(jnp.allclose(kp2, kr)), "2-D append corrupted"
            assert bool(jnp.allclose(vp2, vr))
        # the pool really lands sharded over BOTH axes under jit
        from jax.sharding import NamedSharding, PartitionSpec as P
        kp_s = jax.device_put(kp, NamedSharding(mesh,
                                                P(("data", "model"))))
        ctx2, _, _ = jax.jit(lambda *a: flash_decode_paged(*a, mesh=mesh))(
            q, kn, vn, kp_s, vp, tbl, jnp.asarray([16, 8, 31, 10]), 0)
        print("OK")
    """)


def test_flash_decode_paged_2d_aliased_tables():
    """Cross-request block aliasing through the 2-D combine: a block id
    appearing in TWO slots' tables (a refcounted prefix hit, each inside
    its data shard's sub-pool) reads exactly like a private copy of the
    same rows, and the fused append only ever touches each slot's
    private tail block — the CoW contract the engine enforces means no
    slot appends into a shared id, so aliasing must be invisible to the
    kernel."""
    run_subprocess("""
        import jax, jax.numpy as jnp
        from repro.dist.flash_decode import flash_decode_paged, \\
            pool_sharding_kind
        from repro.kernels import ref
        mesh = make_mesh((2, 4), ("data", "model"))
        B, H, K, D, bl, N = 4, 8, 4, 16, 8, 16
        assert pool_sharding_kind(mesh, N, B) == "2d"
        ks = jax.random.split(jax.random.PRNGKey(2), 5)
        q = jax.random.normal(ks[0], (B, 1, H, D))
        kn = jax.random.normal(ks[1], (B, 1, K, D))
        vn = jax.random.normal(ks[2], (B, 1, K, D))
        kp = jax.random.normal(ks[3], (N, bl, K, D))
        vp = jax.random.normal(ks[4], (N, bl, K, D))
        # slots 0-1 (data shard 0, ids [0,8)) share prefix blocks
        # {0, 5}; slots 2-3 (shard 1, ids [8,16)) share {8, 15}; every
        # slot appends into its own private tail block
        ta = jnp.asarray([[0, 5, 2, -1], [0, 5, 6, -1],
                          [8, 15, 11, 13], [8, 15, 12, -1]], jnp.int32)
        # private twin: duplicate the shared rows into same-shard ids
        # (1 sits on block 0's (data, model) shard, 4 on 5's, ...) so
        # the combine partitions identically and only aliasing differs
        kpp = kp.at[1].set(kp[0]).at[4].set(kp[5]) \\
                .at[9].set(kp[8]).at[14].set(kp[15])
        vpp = vp.at[1].set(vp[0]).at[4].set(vp[5]) \\
                .at[9].set(vp[8]).at[14].set(vp[15])
        tp = jnp.asarray([[0, 5, 2, -1], [1, 4, 6, -1],
                          [8, 15, 11, 13], [9, 14, 12, -1]], jnp.int32)
        pos = jnp.asarray([17, 20, 27, 16], jnp.int32)
        for win in (0, 8):
            run = jax.jit(lambda kk, vv, tt: flash_decode_paged(
                q, kn, vn, kk, vv, tt, pos, win, mesh=mesh))
            ctx_a, kp2, vp2 = run(kp, vp, ta)
            ctx_p, kpp2, vpp2 = run(kpp, vpp, tp)
            err = float(jnp.abs(ctx_a - ctx_p).max())
            assert err < 1e-5, (win, err)
            # aliased run matches the gather oracle too
            kr = ref.paged_append_ref(kp, kn, pos, ta)
            vr = ref.paged_append_ref(vp, vn, pos, ta)
            r = ref.paged_decode_attention_ref(
                q[:, 0], kr, vr, ta, cache_len=pos + 1, window=win)
            assert float(jnp.abs(ctx_a[:, 0] - r).max()) < 1e-5
            # appends landed only in private tail blocks; the shared
            # prefix blocks came through bit-identical
            assert bool(jnp.allclose(kp2, kr)), "2-D append corrupted"
            for b in (0, 5, 8, 15):
                assert bool((kp2[b] == kp[b]).all()), (win, b)
                assert bool((vp2[b] == vp[b]).all()), (win, b)
        print("OK")
    """)


def test_serve_paged_2d_shared_prefix_token_identity():
    """Prefix sharing under 2-D pool sharding respects the combine
    contract: one trie per data-shard sub-pool, admission prefers the
    sub-pool holding the longest match, aliased blocks stay inside the
    owning sub-pool, and a staggered shared-system-prompt batch through
    ``shard_map_flash_paged_2d`` is token-identical to the reuse-off
    run — with the pool whole and the tries empty after drain.

    Prompt tails are several tokens long so matched admissions take the
    tail-prefill path (exact: same kernel class as full prefill over
    identical pool rows).  The zero-prefill decode-ride is deliberately
    NOT exercised here: a ride computes its first token through the
    sharded decode combine, whose reduction order differs from the
    prefill kernel's on a >1-shard mesh — the same near-tie rounding
    caveat ``test_serve_paged_2d_token_identity_vs_dense_sequential``
    documents.  Ride token-identity is pinned bitwise on the
    single-shard paths in test_serve_mixed."""
    run_subprocess("""
        import dataclasses, jax, numpy as np
        from repro.configs import ShapeConfig, get_arch
        from repro.core.pipeline import specialize
        from repro.models import lm
        from repro.serve.engine import ServeEngine

        arch = dataclasses.replace(get_arch("qwen3-8b").reduced(),
                                   n_kv_heads=1)
        shape = ShapeConfig("serve_2d_px", "decode", 64, 4)
        plan = specialize(arch, shape, mesh_axes=("data", "model"),
                          mesh_shape=(2, 4), cache=False)
        assert plan.estimates["kv_residency"] == "paged"
        assert plan.estimates["kv_prefix_reuse"] == "on"
        assert plan.estimates["kv_pool_data_degree"] == 2
        mesh = make_mesh((2, 4), ("data", "model"))
        params = lm.init_params(arch, jax.random.PRNGKey(0),
                                *plan.padded_sizes())

        def run(reuse):
            eng = ServeEngine.from_plan(plan, params, arch=arch,
                                        mesh=mesh, kv_prefix_reuse=reuse)
            assert eng.decode_path == "shard_map_flash_paged_2d"
            assert eng.pool_groups == 2
            bl = eng.block_len
            rng = np.random.default_rng(0)
            sysp = rng.integers(0, arch.vocab_size, bl).astype(np.int32)
            # 5-token tails: matched blocks cover 16 of 21 feed tokens,
            # so admission aliases the prefix and tail-prefills the rest
            prompts = [np.concatenate(
                           [sysp, rng.integers(0, arch.vocab_size, 5)]
                       ).astype(np.int32) for _ in range(4)]
            eng.submit(prompts[0], max_new_tokens=4)
            eng.step()
            eng.step()
            for p in prompts[1:]:
                eng.submit(p, max_new_tokens=4)
            done = eng.run_until_idle(max_ticks=64)
            assert len(done) == 4
            if reuse == "on":
                ps = eng.pressure_stats()
                assert ps["prefix_hits"] >= 1, ps
                # the per-sub-pool tries: one per data shard
                assert eng._prefix is not None \\
                    and eng._prefix.groups == 2
                # the plan sizes a host tier for this geometry, so the
                # engine retains finished trie-indexed blocks in its
                # cold cache after drain; release them to check the
                # pool identity
                assert eng.block_stats()["cached"] >= 1
                assert eng.drop_block_cache() >= 1
                st = eng.block_stats()
                assert st["prefix_trie"] == 0 and st["shared"] == 0
                assert st["cached"] == 0
                assert st["host_free"] == st["host_total"]
            stats = eng.block_stats()
            assert stats["free"] == stats["total"], stats
            return {r.rid: r.out_tokens for r in done}

        assert run("on") == run("off")
        print("OK")
    """, timeout=900)


def test_serve_from_plan_paged_pool_sharded():
    """A paged decode plan served end-to-end on an 8-wide model axis:
    the pool dim really lands sharded, the engine reports the pool-
    sharded path, blocks recycle across a staggered mix, and tokens
    match sequential single-request serving through the same path."""
    run_subprocess("""
        import dataclasses, jax, numpy as np
        from repro.configs import ShapeConfig, get_arch
        from repro.core.pipeline import specialize
        from repro.models import lm
        from repro.serve.engine import ServeEngine

        arch = dataclasses.replace(get_arch("qwen3-8b").reduced(),
                                   n_kv_heads=1)
        shape = ShapeConfig("serve_paged_md", "decode", 64, 4)
        plan = specialize(arch, shape, mesh_axes=("data", "model"),
                          mesh_shape=(1, 8), cache=False)
        assert plan.estimates.get("decode_impl") == "shard_map_flash"
        assert plan.estimates.get("kv_residency") == "paged"
        assert plan.estimates["kv_n_blocks"] % 8 == 0   # pool shardable
        mesh = make_mesh((1, 8), ("data", "model"))
        params = lm.init_params(arch, jax.random.PRNGKey(0),
                                *plan.padded_sizes())
        eng = ServeEngine.from_plan(plan, params, arch=arch, mesh=mesh)
        assert eng.kv_residency == "paged"
        assert eng.decode_path == "shard_map_flash", eng.decode_path
        # the block pool really lands sharded on its pool dim
        kshard = eng.cache["k"].sharding.spec
        assert kshard[1] == "model", kshard
        prompts = [np.arange(5, dtype=np.int32) % arch.vocab_size,
                   (np.arange(11, dtype=np.int32) * 3) % arch.vocab_size,
                   (np.arange(8, dtype=np.int32) * 7) % arch.vocab_size,
                   (np.arange(11, dtype=np.int32) * 5) % arch.vocab_size,
                   (np.arange(5, dtype=np.int32) * 2) % arch.vocab_size]
        for p in prompts:
            eng.submit(p, max_new_tokens=5)
        done = eng.run_until_idle(max_ticks=64)
        assert len(done) == 5 and all(len(r.out_tokens) == 5 for r in done)
        stats = eng.block_stats()
        assert stats["free"] == stats["total"], stats
        a = {r.prompt.tobytes(): r.out_tokens for r in done}
        # sequential single-request runs through the SAME pool-sharded
        # path (same pool size -> same dispatch; a max_batch=1 engine
        # would clamp the pool below the 8-way divisibility)
        for p in prompts[:3]:
            eng2 = ServeEngine.from_plan(plan, params, arch=arch,
                                         mesh=mesh)
            assert eng2.decode_path == "shard_map_flash"
            eng2.submit(p, max_new_tokens=5)
            done2 = eng2.run_until_idle(max_ticks=32)
            assert a[p.tobytes()] == done2[0].out_tokens, (
                p, a[p.tobytes()], done2[0].out_tokens)
        print("OK")
    """, timeout=600)


@pytest.mark.parametrize("name", ["qwen3-8b", "mamba2-2.7b", "hymba-1.5b"])
def test_serve_paged_2d_token_identity_vs_dense_sequential(name):
    """The tentpole acceptance: on a data-degree>1 (2x4) mesh,
    specialize() now records kv_residency=paged with 2-D geometry
    (batch-partitioned sub-pools — the pre-2-D pass forced dense here),
    ServeEngine.from_plan serves it end-to-end through
    ``decode_path == "shard_map_flash_paged_2d"``, and a staggered
    continuous batch is token-identical to the dense sequential oracle
    through the same mesh — across attention / SSM / hybrid archs
    (SSM-only has nothing to page and pins the honest dense fallback).

    The staggered-vs-sequential comparison through the SAME 2-D path is
    exact (the batching/allocator contract).  The cross-residency
    comparison pins per-step fp32 logits within bf16 combine-rounding
    tolerance and tokens exactly — except a *provable* near-tie argmax
    flip (the divergent token must be the oracle's runner-up within a
    tiny logit gap): the paged and dense combines partition the softmax
    sum differently, the same documented rounding caveat as xla-vs-
    flash, and a real bug (wrong block, wrong mask) shows up as an
    O(1) logit error, not a near-tie swap.
    """
    run_subprocess(f"""
        import dataclasses, jax, numpy as np
        from repro.configs import ShapeConfig, get_arch
        from repro.core.pipeline import specialize
        from repro.models import lm
        from repro.serve.engine import ServeEngine

        name = {name!r}

        class Probe(ServeEngine):
            # capture each sampled step's fp32 logits (single-request
            # engines only: one _sample call per emitted token)
            def __init__(self, *a, **kw):
                super().__init__(*a, **kw)
                self.steps = []
            def _sample(self, r, logits, key):
                self.steps.append(np.asarray(
                    logits[:self.arch.vocab_size], np.float32))
                return super()._sample(r, logits, key)

        arch = get_arch(name).reduced()
        if arch.has_attention:
            # GQA-on-wide-TP: kv=1 not shardable by model=4 -> seq spill
            # -> the plan picks shard_map_flash
            arch = dataclasses.replace(arch, n_kv_heads=1)
        shape = ShapeConfig("serve_2d", "decode", 64, 4)
        plan = specialize(arch, shape, mesh_axes=("data", "model"),
                          mesh_shape=(2, 4), cache=False)
        mesh = make_mesh((2, 4), ("data", "model"))
        params = lm.init_params(arch, jax.random.PRNGKey(0),
                                *plan.padded_sizes())
        prompts = [np.arange(5, dtype=np.int32) % arch.vocab_size,
                   (np.arange(11, dtype=np.int32) * 3) % arch.vocab_size,
                   (np.arange(8, dtype=np.int32) * 7) % arch.vocab_size,
                   (np.arange(11, dtype=np.int32) * 5) % arch.vocab_size,
                   (np.arange(5, dtype=np.int32) * 2) % arch.vocab_size]

        if arch.has_attention:
            assert plan.estimates["decode_impl"] == "shard_map_flash"
            assert plan.estimates["kv_residency"] == "paged", \\
                plan.estimates.get("kv_residency")
            assert plan.estimates["kv_pool_data_degree"] == 2
            assert plan.estimates["kv_n_blocks"] % (2 * 4) == 0
            assert plan.estimates["kv_paged_bytes"] \\
                < plan.estimates["kv_dense_bytes"]
            eng = ServeEngine.from_plan(plan, params, arch=arch, mesh=mesh)
            assert eng.kv_residency == "paged"
            assert eng.pool_groups == 2, eng.pool_groups
            assert eng.decode_path == "shard_map_flash_paged_2d", \\
                eng.decode_path
            # the pool really lands sharded over BOTH mesh axes
            kshard = eng.cache["k"].sharding.spec
            assert kshard[1] in (("data", "model"), ["data", "model"]), \\
                kshard
        else:
            assert "kv_residency" not in plan.estimates  # nothing to page
            eng = ServeEngine.from_plan(plan, params, arch=arch, mesh=mesh)
            assert eng.kv_residency == "dense"
        for p in prompts:
            eng.submit(p, max_new_tokens=4)
        done = eng.run_until_idle(max_ticks=64)
        assert len(done) == 5 and all(len(r.out_tokens) == 4 for r in done)
        stats = eng.block_stats()
        assert stats["free"] == stats["total"], stats
        got = {{r.prompt.tobytes(): r.out_tokens for r in done}}

        # dense sequential oracle over the SAME mesh (seq-sharded
        # flash-decode for attention archs)
        dplan = specialize(arch, shape, mesh_axes=("data", "model"),
                           mesh_shape=(2, 4), cache=False,
                           kv_residency="dense")
        for p in prompts:
            ep = Probe.from_plan(plan, params, arch=arch, mesh=mesh)
            ep.submit(p, max_new_tokens=4)
            seq = ep.run_until_idle(max_ticks=32)[0].out_tokens
            # staggered continuous batch == sequential single-request
            # through the SAME path: exact
            assert got[p.tobytes()] == seq, (p, got[p.tobytes()], seq)

            ed = Probe.from_plan(dplan, params, arch=arch, mesh=mesh,
                                 max_batch=1)
            assert ed.kv_residency == "dense"
            ed.submit(p, max_new_tokens=4)
            dseq = ed.run_until_idle(max_ticks=32)[0].out_tokens
            # cross-residency: token-identical, excusing only a provable
            # near-tie argmax flip (runner-up within a tiny gap, logits
            # within bf16 combine-rounding tolerance)
            for i, (tp, td) in enumerate(zip(seq, dseq)):
                if tp == td:
                    continue
                lp, ld = ep.steps[i], ed.steps[i]
                diff = float(np.abs(lp - ld).max())
                gap = float(ld[td] - ld[tp])
                assert diff < 0.3 and 0.0 <= gap < 0.15, (
                    "paged-2d diverged from the dense oracle outside "
                    "near-tie tolerance", p, i, tp, td, diff, gap)
                break          # prefixes differ from here on
        print("OK", name)
    """, timeout=900)


def test_serve_batched_sampling_on_sharded_logits_matches_probe():
    """On the 2x4 mesh the decode and prefill logits arrive sharded over
    data x model.  The engine's one-program sampling of every row gives
    the tokens a probe engine (per-row ``_sample``) gives for the same
    staggered schedule, greedy and sampled requests mixed."""
    run_subprocess("""
        import dataclasses, jax, numpy as np
        from repro.configs import ShapeConfig, get_arch
        from repro.core.pipeline import specialize
        from repro.models import lm
        from repro.serve.engine import ServeEngine

        class Probe(ServeEngine):
            def _sample(self, r, logits, key):
                return super()._sample(r, logits, key)

        arch = dataclasses.replace(get_arch("qwen3-8b").reduced(),
                                   n_kv_heads=1)
        shape = ShapeConfig("serve_2d", "decode", 64, 4)
        plan = specialize(arch, shape, mesh_axes=("data", "model"),
                          mesh_shape=(2, 4), cache=False)
        mesh = make_mesh((2, 4), ("data", "model"))
        params = lm.init_params(arch, jax.random.PRNGKey(0),
                                *plan.padded_sizes())

        def serve(cls):
            eng = cls.from_plan(plan, params, arch=arch, mesh=mesh)
            assert eng.decode_path == "shard_map_flash_paged_2d"
            specs = []
            run = eng._sample_rows

            def record(logits, *a, **kw):
                specs.append(logits.sharding.spec)
                return run(logits, *a, **kw)

            eng._sample_rows = record
            for i in range(5):
                p = (np.arange(5 + 2 * i, dtype=np.int32) * (i + 1)
                     % arch.vocab_size)
                eng.submit(p, max_new_tokens=5, temperature=0.7 * (i % 2))
                eng.step()
            done = eng.run_until_idle(max_ticks=64)
            assert len(done) == 5
            return ({r.rid: r.out_tokens for r in done},
                    eng.pressure_stats(), specs)

        got, stats, specs = serve(ServeEngine)
        want, pstats, pspecs = serve(Probe)
        assert got == want, (got, want)
        assert stats["sampled_batched"] == 25, stats
        assert stats["sampled_per_slot"] == 0, stats
        assert (pstats["sampled_per_slot"], pspecs) == (25, [])
        assert any("model" in str(s) for s in specs), specs
        print("OK")
    """, timeout=600)


def test_moe_shard_map_matches_gshard_on_mesh():
    run_subprocess("""
        import jax, jax.numpy as jnp
        from repro.models.moe import (MoEParams, moe_gshard_einsum,
                                      moe_shard_map)
        mesh = make_mesh((2, 4), ("data", "model"))
        B, S, d, E, ff, k = 4, 32, 16, 8, 32, 2
        keys = jax.random.split(jax.random.PRNGKey(0), 3)
        p = MoEParams(
            router=jax.random.normal(keys[0], (d, E)) * 0.5,
            wi=jax.random.normal(keys[1], (E, d, 2 * ff)) * 0.1,
            wo=jax.random.normal(keys[2], (E, ff, d)) * 0.1)
        from jax.sharding import NamedSharding, PartitionSpec as P
        x = jax.random.normal(jax.random.PRNGKey(9), (B, S, d))
        xs = jax.device_put(x, NamedSharding(mesh, P("data", None, None)))
        y1, a1 = jax.jit(lambda x: moe_gshard_einsum(
            x, p, top_k=k, capacity_factor=4.0))(xs)
        y2, a2 = jax.jit(lambda x: moe_shard_map(
            x, p, top_k=k, capacity_factor=4.0, mesh=mesh))(xs)
        # capacity groups differ (global vs per-shard) so a few border
        # tokens may drop differently; demand bulk agreement
        diff = jnp.abs(y1 - y2)
        frac_close = float(jnp.mean((diff < 1e-3).astype(jnp.float32)))
        assert frac_close > 0.9, frac_close
        print("OK")
    """)


def test_compressed_psum_multi_shard():
    run_subprocess("""
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.dist.collectives import compressed_psum
        mesh = make_mesh((8,), ("data",))
        x = jnp.arange(8 * 16, dtype=jnp.float32).reshape(8, 16) / 7.0
        def f(xs):
            y, err = compressed_psum(xs[0], "data")
            return y[None], err[None]
        y, err = jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=P("data", None),
            out_specs=(P("data", None), P("data", None))))(x)
        want = jnp.mean(x, axis=0)
        got = y[0]
        rel = float(jnp.abs(got - want).max() / jnp.abs(want).max())
        assert rel < 0.02, rel
        print("OK")
    """)


def test_combine_topology_matrix_ring_bidir_vs_flat():
    """The combine-topology oracle matrix: ring and bidirectional-ring
    softmax combines pinned against the flat-psum combine and the gather
    oracle across model degrees {2, 4, 8} for the dense seq-sharded
    kernel, the 1-D pool-sharded paged kernel, and the 2-D paged
    placement.

    Contracts (measured, not aspirational): ring == bidir BITWISE (both
    fold the same source-indexed gathered buffer in the same sequential
    order — the two ppermute arms only change how the buffer fills);
    ring vs flat agree to the last ulp (flat's psum is fused with the
    exp/mul rescale by XLA and re-rounds differently — 1-ulp class, not
    a reduction-order class, so a loose 1e-6); and every topology
    matches the unsharded gather oracle within the 1e-5 bound every
    other decode test in this file pins."""
    run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.dist.flash_decode import (combine_topology, flash_decode,
                                             flash_decode_paged)
        from repro.kernels import ref

        TOPOS = ("flat", "ring", "bidir")

        def check(outs, oracle, tag):
            assert np.array_equal(outs["ring"], outs["bidir"]), tag
            d = np.abs(outs["ring"] - outs["flat"]).max()
            assert d < 1e-6, (tag, d)
            for t in TOPOS:
                e = np.abs(outs[t] - np.asarray(oracle)).max()
                assert e < 1e-5, (tag, t, e)

        # predicate: 8 host devices cap the natural degree at 8, all
        # flat; overrides force the wire pattern; a degenerate model
        # axis has no cross-shard combine so even an override is flat
        for dsz, msz in ((4, 2), (2, 4), (1, 8)):
            m = make_mesh((dsz, msz), ("data", "model"))
            assert combine_topology(m) == "flat"
            assert combine_topology(m, override="ring") == "ring"
            assert combine_topology(m, override="bidir") == "bidir"
        m1 = make_mesh((8, 1), ("data", "model"))
        assert combine_topology(m1) == "flat"
        assert combine_topology(m1, override="bidir") == "flat"
        try:
            combine_topology(make_mesh((1, 8), ("data", "model")),
                             override="hypercube")
            raise SystemExit("expected ValueError on unknown topology")
        except ValueError:
            pass

        # dense seq-sharded kernel across model degrees 2/4/8
        B, S, H, K, D = 4, 64, 8, 4, 16
        ks = jax.random.split(jax.random.PRNGKey(0), 5)
        q = jax.random.normal(ks[0], (B, 1, H, D))
        kn = jax.random.normal(ks[1], (B, 1, K, D))
        vn = jax.random.normal(ks[2], (B, 1, K, D))
        kc = jax.random.normal(ks[3], (B, S, K, D))
        vc = jax.random.normal(ks[4], (B, S, K, D))
        pos = jnp.asarray([10, 40, 63, 5], jnp.int32)
        kr = ref.decode_append_ref(kc, kn, pos)
        vr = ref.decode_append_ref(vc, vn, pos)
        r = ref.decode_attention_ref(q[:, 0], kr, vr,
                                     cache_len=pos + 1, window=0)
        for dsz, msz in ((4, 2), (2, 4), (1, 8)):
            mesh = make_mesh((dsz, msz), ("data", "model"))
            outs = {}
            for t in TOPOS:
                ctx, _, _ = jax.jit(lambda *a, t=t: flash_decode(
                    *a, mesh=mesh, combine=t))(q, kn, vn, kc, vc, pos, 0)
                outs[t] = np.asarray(ctx[:, 0])
            check(outs, r, ("dense", msz))

        # 1-D pool-sharded paged kernel across model degrees 2/4/8
        # (B=3 keeps the batch unpartitionable over data>1, pinning the
        # replicated-pool 1-D combine)
        Bp, bl, N = 3, 8, 16
        kp = jax.random.normal(jax.random.split(ks[3])[0], (N, bl, K, D))
        vp = jax.random.normal(jax.random.split(ks[4])[0], (N, bl, K, D))
        tbl = jnp.asarray([[0, 9, 3, -1], [14, 2, -1, -1],
                           [5, 7, 11, 13]], jnp.int32)
        ppos = jnp.asarray([16, 8, 31], jnp.int32)
        kpr = ref.paged_append_ref(kp, kn[:Bp], ppos, tbl)
        vpr = ref.paged_append_ref(vp, vn[:Bp], ppos, tbl)
        pr = ref.paged_decode_attention_ref(
            q[:Bp, 0], kpr, vpr, tbl, cache_len=ppos + 1, window=0)
        for dsz, msz in ((4, 2), (2, 4), (1, 8)):
            mesh = make_mesh((dsz, msz), ("data", "model"))
            outs = {}
            for t in TOPOS:
                ctx, _, _ = jax.jit(lambda *a, t=t: flash_decode_paged(
                    *a, mesh=mesh, combine=t))(
                        q[:Bp], kn[:Bp], vn[:Bp], kp, vp, tbl, ppos, 0)
                outs[t] = np.asarray(ctx[:, 0])
            check(outs, pr, ("paged-1d", msz))

        # 2-D placement (batch-partitioned sub-pools, model degree 4):
        # the combine override must plumb through the 2-D combine too
        from repro.dist.flash_decode import pool_sharding_kind
        mesh2 = make_mesh((2, 4), ("data", "model"))
        t2 = jnp.asarray([[0, 5, 3, -1], [7, 2, -1, -1],
                          [8, 15, 11, 13], [9, 14, -1, -1]], jnp.int32)
        p2 = jnp.asarray([16, 8, 31, 10], jnp.int32)
        assert pool_sharding_kind(mesh2, N, B) == "2d"
        k2r = ref.paged_append_ref(kp, kn, p2, t2)
        v2r = ref.paged_append_ref(vp, vn, p2, t2)
        r2 = ref.paged_decode_attention_ref(
            q[:, 0], k2r, v2r, t2, cache_len=p2 + 1, window=0)
        outs = {}
        for t in TOPOS:
            ctx, _, _ = jax.jit(lambda *a, t=t: flash_decode_paged(
                *a, mesh=mesh2, combine=t))(q, kn, vn, kp, vp, t2, p2, 0)
            outs[t] = np.asarray(ctx[:, 0])
        check(outs, r2, ("paged-2d", 4))
        print("OK")
    """, timeout=600)


def test_serve_from_plan_ring_combine_end_to_end():
    """A plan-recorded ring combine served end-to-end: specialize() with
    the ``combine_topology="ring"`` override records the decision (8
    host devices cannot exceed the flat<=8 threshold naturally), the
    RunCfg carries it through ``from_plan`` without any engine-side
    kwarg, the engine reports it in telemetry, and a staggered
    continuous batch through the ring combine is token-identical to
    sequential single-request serving through the same path."""
    run_subprocess("""
        import dataclasses, jax, numpy as np
        from repro.configs import ShapeConfig, get_arch
        from repro.core.pipeline import specialize
        from repro.models import lm
        from repro.serve.engine import ServeEngine

        arch = dataclasses.replace(get_arch("qwen3-8b").reduced(),
                                   n_kv_heads=1)
        shape = ShapeConfig("serve_ring", "decode", 32, 2)
        plan = specialize(arch, shape, mesh_axes=("data", "model"),
                          mesh_shape=(1, 8), cache=False,
                          kv_residency="dense", combine_topology="ring")
        assert plan.estimates.get("decode_impl") == "shard_map_flash"
        assert plan.estimates["combine_topology"] == "ring"
        assert plan.comm.combine_topology == "ring"
        # the decision log narrates the override, not a modeled choice
        recs = [(d, w) for _, s, d, w in plan.log
                if s == "combine_topology"]
        assert recs and recs[-1][0] == "ring" \\
            and "forced by options" in recs[-1][1], recs

        mesh = make_mesh((1, 8), ("data", "model"))
        params = lm.init_params(arch, jax.random.PRNGKey(0),
                                *plan.padded_sizes())
        eng = ServeEngine.from_plan(plan, params, arch=arch, mesh=mesh)
        assert eng.decode_path == "shard_map_flash", eng.decode_path
        assert eng.combine_topology == "ring", eng.combine_topology
        assert eng.telemetry()["combine_topology"] == "ring"

        prompts = [np.arange(5, dtype=np.int32) % arch.vocab_size,
                   (np.arange(11, dtype=np.int32) * 3) % arch.vocab_size,
                   (np.arange(8, dtype=np.int32) * 7) % arch.vocab_size]
        eng.submit(prompts[0], max_new_tokens=5)
        eng.step()
        for p in prompts[1:]:
            eng.submit(p, max_new_tokens=5)
        done = eng.run_until_idle(max_ticks=64)
        assert len(done) == 3 and all(len(r.out_tokens) == 5 for r in done)
        a = {r.prompt.tobytes(): r.out_tokens for r in done}
        for p in prompts:
            eng2 = ServeEngine.from_plan(plan, params, arch=arch,
                                         mesh=mesh, max_batch=1)
            assert eng2.combine_topology == "ring"
            eng2.submit(p, max_new_tokens=5)
            done2 = eng2.run_until_idle(max_ticks=32)
            assert a[p.tobytes()] == done2[0].out_tokens, (
                p, a[p.tobytes()], done2[0].out_tokens)
        print("OK")
    """, timeout=600)


def test_train_step_fsdp_dp_multidevice():
    """The fsdp_dp lowered train step executes on a real (2,4) mesh."""
    run_subprocess("""
        import dataclasses, jax, jax.numpy as jnp
        from repro.configs import ShapeConfig, get_arch
        from repro.core.pipeline import specialize
        from repro.core.passes.lowering import lower_train_step
        from repro.models import synthetic_batch
        from repro.optim import OptConfig
        from repro.train.trainer import Trainer, TrainerConfig
        mesh = make_mesh((2, 4), ("data", "model"))
        arch = get_arch("qwen3-8b").reduced()
        shape = ShapeConfig("t", "train", 64, 8)
        plan = specialize(arch, shape, mesh_axes=("data", "model"),
                          mesh_shape=(2, 4))
        tr = Trainer(plan, mesh, TrainerConfig(n_steps=1, ckpt_every=0),
                     opt_cfg=OptConfig(total_steps=4),
                     arch=arch, shape=shape)
        state = tr.init_state()
        batch = synthetic_batch(arch, shape, jax.random.PRNGKey(1))
        state, m = tr.step_fn(state, batch)
        assert bool(jnp.isfinite(m["loss"]))
        print("OK strategy=", plan.estimates.get("strategy"))
    """, timeout=420)
