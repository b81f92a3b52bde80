"""The repro.dist layer + the perf work that rides on it.

Covers what the seed tests do not: int8 round-trips on non-128-multiple
shapes, compressed_psum over a >1-size axis, resolve_pspec divisibility
repair on awkward dims, the plan cache, the CommunicationPass
compressed-schedule decision, and the causal flash-attention grid
pruning (grid math + bit-identical output).
"""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ShapeConfig
from repro.core.costmodel import compressed_ratio
from repro.core.pipeline import (clear_plan_cache, plan_cache_stats,
                                 specialize)
from repro.dist.collectives import dequantize_int8, ef_compress, quantize_int8
from repro.dist.sharding import cache_pspecs, mesh_sizes, resolve_pspec
from repro.launch.mesh import make_mesh
from repro.kernels.flash_attention import flash_attention, kv_grid_steps

SRC = str(Path(__file__).resolve().parents[1] / "src")


# ---------------- int8 quantization on awkward shapes ----------------

@pytest.mark.parametrize("shape", [(1,), (7,), (127,), (129,), (3, 5),
                                   (257,), (2, 130)])
def test_int8_roundtrip_non_multiple_shapes(shape):
    x = jnp.asarray(
        np.random.default_rng(0).standard_normal(shape) * 5, jnp.float32)
    q, scales, pad = quantize_int8(x)
    assert q.dtype == jnp.int8
    assert (int(np.prod(shape)) + pad) % 128 == 0
    xr = dequantize_int8(q, scales, pad, x.shape)
    assert xr.shape == x.shape
    amax = float(jnp.abs(x).max())
    assert float(jnp.abs(xr - x).max()) <= amax / 254 * 1.001 + 1e-6


def test_int8_roundtrip_zeros_and_tiny():
    for x in (jnp.zeros((5,)), jnp.full((300,), 1e-7)):
        q, s, pad = quantize_int8(x)
        xr = dequantize_int8(q, s, pad, x.shape)
        assert float(jnp.abs(xr - x).max()) <= 1e-6


def test_ef_compress_keeps_residual_dtype():
    g = jnp.linspace(-1, 1, 300)
    gh, err = ef_compress(g, None)
    assert err.dtype == jnp.float32 and gh.dtype == g.dtype
    gh, err2 = ef_compress(g, jnp.zeros_like(g, jnp.bfloat16))
    assert err2.dtype == jnp.bfloat16


# ---------------- compressed_psum over a real >1 axis ----------------

def test_compressed_psum_axis_size_two_awkward_shape():
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent("""
            import jax, jax.numpy as jnp
            from jax.sharding import PartitionSpec as P
            from repro.dist.collectives import compressed_psum
            from repro.launch.mesh import make_mesh
            mesh = make_mesh((2,), ("data",))
            x = jnp.arange(2 * 37, dtype=jnp.float32).reshape(2, 37) / 5.0
            def f(xs):
                y, err = compressed_psum(xs[0], "data")
                return y[None], err[None]
            y, err = jax.jit(jax.shard_map(
                f, mesh=mesh, in_specs=P("data", None),
                out_specs=(P("data", None), P("data", None))))(x)
            want = jnp.mean(x, axis=0)
            rel = float(jnp.abs(y[0] - want).max() / jnp.abs(want).max())
            assert rel < 0.02, rel
            # the residual is exactly what dequantization dropped
            assert float(jnp.abs(err).max()) <= float(jnp.abs(x).max()) / 254 * 1.01
            print("OK")
        """)],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "PYTHONPATH": SRC,
             "XLA_FLAGS": "--xla_force_host_platform_device_count=2"})
    assert out.returncode == 0, out.stderr[-3000:]


# ---------------- resolve_pspec repair ----------------

def test_resolve_pspec_divisibility_repair_awkward_dims():
    sizes = {"pod": 2, "data": 4, "model": 8}
    rules = {"batch": ("pod", "data"), "embed": ("data", "model"),
             "heads": "model", "ff": "model"}
    # 6 % (2*4) != 0 -> batch dim repaired to unsharded
    spec = resolve_pspec(rules, (6, 64), ("batch", "embed"), sizes)
    assert tuple(spec) == (None, ("data", "model"))
    # 96 % 32 == 0 -> keeps the full tuple
    spec = resolve_pspec(rules, (96, 30), ("embed", "heads"), sizes)
    assert spec[0] == ("data", "model")
    assert spec[1] is None              # model already used AND 30 % 8 != 0
    # uniqueness: first dim wins the contested axis
    spec = resolve_pspec(rules, (16, 16), ("heads", "ff"), sizes)
    assert tuple(spec) == ("model", None)
    # rules naming axes absent from this mesh are dropped, not crashed
    spec = resolve_pspec({"batch": ("pod", "data")}, (8,), ("batch",),
                         {"data": 4})
    assert tuple(spec) == ("data",)


def test_cache_pspecs_follows_seq_spill():
    plan = specialize("qwen2-vl-72b", "decode_32k")
    assert plan.estimates["decode_impl"] == "shard_map_flash"
    cache_shapes = {
        "pos": jax.ShapeDtypeStruct((), jnp.int32),
        "k": jax.ShapeDtypeStruct((80, 128, 32768, 8, 128), jnp.bfloat16),
        "v": jax.ShapeDtypeStruct((80, 128, 32768, 8, 128), jnp.bfloat16),
    }
    sizes = {"data": 16, "model": 16}
    specs = cache_pspecs(plan, None, cache_shapes, sizes)
    assert tuple(specs["k"])[2] == "model"      # seq dim carries the TP axis
    assert tuple(specs["pos"]) == ()


def test_mesh_sizes_accepts_all_mesh_flavors():
    from repro.core.costmodel import MeshModel
    mm = MeshModel(axes=("data", "model"), shape=(4, 2))
    assert mesh_sizes(mm) == {"data": 4, "model": 2}
    assert mesh_sizes({"data": 4}) == {"data": 4}
    m = make_mesh((1,), ("data",))
    assert mesh_sizes(m) == {"data": 1}


# ---------------- plan cache ----------------

def test_plan_cache_hit_miss_and_isolation(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_PLAN_DIR", str(tmp_path))
    clear_plan_cache()
    p1 = specialize("qwen3-8b", "train_4k")
    p2 = specialize("qwen3-8b", "train_4k")
    stats = plan_cache_stats()
    assert stats["hits"] == 1 and stats["misses"] == 1
    # warm hits are zero-copy: the SAME immutable artifact comes back
    assert p1 is p2 and p1.to_json() == p2.to_json()
    # different key -> miss
    specialize("qwen3-8b", "decode_32k")
    assert plan_cache_stats()["misses"] == 2
    # cache=False bypasses lookup and insertion entirely
    specialize("qwen3-8b", "train_4k", cache=False)
    stats = plan_cache_stats()
    assert stats["hits"] == 1 and stats["size"] == 2
    # the frozen artifact cannot be poisoned: mutation raises instead
    with pytest.raises(TypeError):
        p2.estimates["poison"] = 1.0
    p3 = specialize("qwen3-8b", "train_4k")
    assert "poison" not in p3.estimates
    # dropping the memory tier falls back to the on-disk artifact —
    # bit-identical content, same hash
    from repro.core import planstore
    store = planstore.get_store()
    store.clear()
    p4 = specialize("qwen3-8b", "train_4k")
    assert plan_cache_stats()["disk_hits"] == 1
    assert p4 == p1 and p4.content_hash() == p1.content_hash()


# ---------------- compressed-schedule decision ----------------

def test_communication_pass_compresses_when_collective_bound():
    """Small-batch TP fine-tuning: DP grad allreduce dominates -> int8+EF."""
    shape = ShapeConfig("cb", "train", 128, 8)
    plan = specialize("qwen3-8b", shape, mesh_axes=("data", "model"),
                      mesh_shape=(8, 2))
    assert plan.comm.compress_grads
    assert plan.comm.compresses_gradients
    raw = plan.estimates["est_collective_s_raw"]
    comp = plan.estimates["est_collective_s_int8"]
    assert raw > 0 and comp == pytest.approx(raw * compressed_ratio(8))
    assert comp < 0.6 * raw                     # the modeled volume cut
    assert plan.estimates["est_collective_s"] == pytest.approx(comp)
    assert any(e[1] == "grad_compression" and "int8" in e[2]
               for e in plan.log)
    # compute-bound big-batch training keeps the raw reduction
    plan2 = specialize("qwen3-8b", "train_4k")
    assert not plan2.comm.compress_grads
    assert any(e[1] == "grad_compression" and e[2] == "off"
               for e in plan2.log)


def test_communication_pass_records_lowered_wire():
    """When compression is on AND the wire gate admits the step, the
    plan records that the cut is lowered (int16 code sums on the wire),
    not merely modeled — with the DP degree in the estimates, a
    narrative decision-log entry, and the flag surviving the frozen
    round-trip.  A compressed plan the gate rejects records the honest
    post-reduce fallback instead."""
    from repro.core.plan import FrozenPlan

    shape = ShapeConfig("cb_low", "train", 128, 8)
    plan = specialize("qwen3-8b", shape, mesh_axes=("data", "model"),
                      mesh_shape=(8, 2))
    assert plan.comm.compress_grads and plan.comm.compress_lowered
    assert plan.estimates["grad_compress_lowered"] == 8.0   # the DP degree
    recs = [(d, w) for _, s, d, w in plan.log
            if s == "grad_compress_lowering"]
    assert recs and "int16" in recs[-1][0] and "dp=8" in recs[-1][0]
    assert "int16" in recs[-1][1]           # headroom narrative
    rt = FrozenPlan.from_json(plan.to_json())
    assert rt.comm.compress_lowered and rt == plan

    # forced compression on a 1-wide data axis: nothing to reduce
    # across, so the gate refuses and the record says post-reduce
    plan2 = specialize("qwen3-8b", ShapeConfig("cb_pr", "train", 128, 8),
                       mesh_axes=("data", "model"), mesh_shape=(1, 2),
                       grad_compression="on")
    assert plan2.comm.compress_grads and not plan2.comm.compress_lowered
    assert "grad_compress_lowered" not in plan2.estimates
    recs2 = [d for _, s, d, _ in plan2.log if s == "grad_compress_lowering"]
    assert recs2 and recs2[-1] == "post-reduce EF"


def test_communication_pass_chooses_and_records_combine_topology():
    """Decode plans choose a model-axis combine topology per mesh
    geometry (calibrated thresholds: flat <= 8 < ring <= 16 < bidir),
    record it with its hop count and a hop-comparison narrative, honor
    the specialize() override, and carry it through the frozen
    artifact — the same choose-and-record shape as kv_residency."""
    from repro.core.plan import FrozenPlan

    dec = ShapeConfig("ct_dec", "decode", 256, 8)
    plan = specialize("qwen3-8b", dec, mesh_axes=("data", "model"),
                      mesh_shape=(1, 8))
    assert plan.comm.combine_topology == "flat"
    assert plan.estimates["combine_topology"] == "flat"
    assert plan.estimates["combine_hops"] == 42.0     # 6 * (8 - 1)
    recs = [(d, w) for _, s, d, w in plan.log if s == "combine_topology"]
    assert recs and recs[-1][0] == "flat"
    rt = FrozenPlan.from_json(plan.to_json())
    assert rt.comm.combine_topology == "flat" and rt == plan

    # wider modeled meshes cross the thresholds (no host devices
    # needed: the pass works on the modeled mesh geometry)
    ring = specialize("qwen3-8b", dec, mesh_axes=("data", "model"),
                      mesh_shape=(1, 16))
    assert ring.estimates["combine_topology"] == "ring"
    assert ring.estimates["combine_hops"] == 15.0
    why = [w for _, s, _, w in ring.log if s == "combine_topology"][-1]
    assert "hop" in why
    bidir = specialize("qwen3-8b", dec, mesh_axes=("data", "model"),
                       mesh_shape=(1, 32))
    assert bidir.estimates["combine_topology"] == "bidir"
    assert bidir.estimates["combine_hops"] == 16.0    # ceil(31 / 2)

    # the override is the ops escape hatch, recorded as forced
    forced = specialize("qwen3-8b", dec, mesh_axes=("data", "model"),
                        mesh_shape=(1, 8), combine_topology="ring")
    assert forced.comm.combine_topology == "ring"
    whyf = [w for _, s, _, w in forced.log if s == "combine_topology"][-1]
    assert "forced by options" in whyf

    # a degenerate model axis records flat: no cross-shard combine
    one = specialize("qwen3-8b", ShapeConfig("ct_one", "decode", 256, 8),
                     mesh_axes=("data", "model"), mesh_shape=(1, 1))
    assert one.estimates["combine_topology"] == "flat"
    assert one.estimates["combine_hops"] == 0.0
    # train plans have no decode combine to choose
    assert "combine_topology" not in \
        specialize("qwen3-8b", "train_4k").estimates


# ---------------- causal grid pruning ----------------

def test_causal_grid_steps_halved_at_4k():
    full = kv_grid_steps(4096, 512, 512, causal=True, prune=False)
    pruned = kv_grid_steps(4096, 512, 512, causal=True, prune=True)
    assert full == 64 and pruned == 36          # (n/2)*(n+1) vs n^2, n=8
    assert pruned / full == (8 + 1) / (2 * 8)   # -> 1/2 for large n
    # large-n ratio approaches exactly half
    n = 4096 // 64
    assert kv_grid_steps(4096, 64, 64) / kv_grid_steps(
        4096, 64, 64, prune=False) == (n + 1) / (2 * n)
    # rectangular tiles keep the full grid (packing needs square tiles)
    assert kv_grid_steps(4096, 512, 1024, causal=True) == 8 * 4
    assert kv_grid_steps(4096, 512, 1024, causal=False) == 8 * 4


def test_partitioning_emits_square_tiles_for_causal():
    """The plan's own tile choice must engage the packed-causal grid."""
    plan = specialize("qwen3-8b", "train_4k")
    bp = plan.partitions["flash_attention"].blocks
    assert bp["block_q"] == bp["block_kv"]
    pruned = kv_grid_steps(4096, bp["block_q"], bp["block_kv"])
    full = kv_grid_steps(4096, bp["block_q"], bp["block_kv"], prune=False)
    assert pruned / full <= 0.6                 # ~half at S=4k
    # non-causal archs keep the wide-kv rectangular tiles
    plan2 = specialize("hubert-xlarge", "train_4k")
    bp2 = plan2.partitions["flash_attention"].blocks
    assert bp2["block_kv"] >= bp2["block_q"]


@pytest.mark.parametrize("S,block", [(256, 64), (192, 64)])  # even + odd n
def test_causal_pruned_bit_identical(S, block):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, S, 4, 32))
    k = jax.random.normal(ks[1], (1, S, 2, 32))
    v = jax.random.normal(ks[2], (1, S, 2, 32))
    o_pruned = flash_attention(q, k, v, block_q=block, block_kv=block,
                               interpret=True, prune=True)
    o_full = flash_attention(q, k, v, block_q=block, block_kv=block,
                             interpret=True, prune=False)
    assert np.array_equal(np.asarray(o_pruned), np.asarray(o_full))


def test_causal_pruned_windowed_matches_oracle():
    from repro.kernels import ref
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (2, 256, 4, 32))
    k = jax.random.normal(ks[1], (2, 256, 2, 32))
    v = jax.random.normal(ks[2], (2, 256, 2, 32))
    o = flash_attention(q, k, v, causal=True, window=48, block_q=64,
                        block_kv=64, interpret=True)
    r = ref.flash_attention_ref(q, k, v, causal=True, window=48)
    assert float(jnp.abs(o - r).max()) < 1e-5
