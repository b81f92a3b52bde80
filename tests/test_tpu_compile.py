"""The main-path Pallas kernels compile for a TPU v5e chip at real widths.

Interpret-mode tests (test_kernels.py, test_paged_kv.py) check what the
kernels compute; they cannot see what the chip's compiler refuses:
blocks that break the (8, 128) tiling, in-kernel reshapes Mosaic cannot
lay out, VMEM over the scoped limit.  These compile each kernel for a
described (not attached) v5e chip and assert the HLO holds the Mosaic
custom call.  The topology is described inside a fixture, never while a
module is imported, so every xdist worker collects the same tests.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.decode_attention import (decode_attention,
                                            paged_decode_attention)
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssd_scan import ssd_scan
from repro.kernels.tiled_matmul import tiled_matmul

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's executables are written to the persistent cache
    # but cannot be read back without a chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _paged(B, H, K, D, bl, window=0):
    n = B * 8
    return (functools.partial(paged_decode_attention, window=window),
            [((B, H, D), BF16), ((n, bl, K, D), BF16),
             ((n, bl, K, D), BF16), ((B, 8), I32)],
            {"cache_len": ((B,), I32)})


def _flash(H, K, D, bq, bkv, window=0, S=4096):
    return (functools.partial(flash_attention, block_q=bq, block_kv=bkv,
                              window=window),
            [((1, S, H, D), BF16), ((1, S, K, D), BF16),
             ((1, S, K, D), BF16)], {})


def _decode(H, K, D, window=0, S=4096, B=8):
    return (functools.partial(decode_attention, window=window),
            [((B, H, D), BF16), ((B, S, K, D), BF16),
             ((B, S, K, D), BF16)],
            {"cache_len": ((), I32)})


def _ssd(H, P, N, S=4096, chunk=256):
    return (functools.partial(ssd_scan, chunk=chunk),
            [((1, S, H, P), F32), ((1, S, H), F32), ((H,), F32),
             ((1, S, H, N), F32), ((1, S, H, N), F32)], {})


# widths: qwen3-8b (H 32, K 8, D 128), hymba-1.5b (H 25, K 5, D 64,
# window 1024, 50 SSM heads of 64 with state 16), mamba2-2.7b (80 SSM
# heads of 64, state 128)
CASES = {
    "paged_decode_qwen3": _paged(8, 32, 8, 128, 256),
    "paged_decode_hymba": _paged(8, 25, 5, 64, 256, window=1024),
    "flash_packed_qwen3": _flash(32, 8, 128, 512, 512),
    "flash_plain_qwen3": _flash(32, 8, 128, 512, 1024),
    "flash_packed_hymba": _flash(25, 5, 64, 512, 512, window=1024),
    "flash_plain_hymba": _flash(25, 5, 64, 512, 1024, window=1024),
    "decode_qwen3": _decode(32, 8, 128),
    "decode_hymba": _decode(25, 5, 64, window=1024),
    "ssd_scan_mamba2": _ssd(80, 64, 128),
    "ssd_scan_hymba": _ssd(50, 64, 16),
    "tiled_matmul_qwen3_ffn": (tiled_matmul, [((4096, 4096), BF16),
                                              ((4096, 12288), BF16)], {}),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, case):
    fn, args, kwargs = CASES[case]

    def sds(spec):
        shape, dtype = spec
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(lambda *a, **k: fn(*a, **k)).lower(
        *map(sds, args), **{k: sds(v) for k, v in kwargs.items()}).compile()
    assert "tpu_custom_call" in compiled.as_text(), case


def test_latent_decode_step_fits_one_v5e(one_chip):
    """The serving decode of deepseek-v2-lite's one-chip share (8 of 64
    experts held, 27 layers) over the plan's latent pool compiles for a
    v5e and fits its HBM: the engine donates the cache, and no pass
    may copy a 6 GiB pool (the compiler refuses a program that does not
    fit)."""
    import dataclasses

    from repro.configs import ShapeConfig, get_arch
    from repro.core.passes.lowering import build_run_cfg
    from repro.core.pipeline import specialize
    from repro.models import lm

    arch = dataclasses.replace(get_arch("deepseek-v2-lite"),
                               n_routed_experts=8)
    plan = specialize(arch, ShapeConfig("serve", "decode", 6144, 32),
                      mesh_axes=("data", "model"), mesh_shape=(1, 1),
                      cache=False)
    cfg = build_run_cfg(plan, arch, None)
    e = plan.estimates

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(
        lambda k: lm.init_params(arch, k), jax.random.PRNGKey(0)))
    cache = on_chip(jax.eval_shape(lambda: lm.init_paged_cache(
        arch, 32, 6144, int(e["kv_block_len"]), int(e["kv_n_blocks"]))))
    tokens = jax.ShapeDtypeStruct((32, 1), I32, sharding=one_chip)
    compiled = jax.jit(
        lambda p, c, b: lm.decode_step(arch, p, c, b, cfg, stats=True),
        donate_argnums=1).lower(params, cache, {"tokens": tokens}).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes > 5 * 2**30          # the pool, in place
    assert mem.temp_size_in_bytes < 2 * 2**30
