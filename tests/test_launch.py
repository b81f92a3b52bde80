"""The launchers' main path on the CPU, and the set-up that decides which
device the program runs on: meshes, depth cuts, the device-kind table,
the compile cache, kernel dispatch, and prefill placement."""

import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import numpy as np
import pytest
from jax.sharding import AxisType

from repro.configs import ShapeConfig, run_arch
from repro.core.pipeline import specialize
from repro.hw import target_for_device
from repro.launch.mesh import make_host_mesh, make_mesh
from repro.launch.serve import make_prompts, serve
from repro.launch.train import train

ROOT = Path(__file__).resolve().parents[1]


def test_meshes_have_auto_axes():
    """Explicit axes (jax.make_mesh's default) reject the program's
    P.UNCONSTRAINED hints and unannotated gathers."""
    for mesh in (make_host_mesh(), make_mesh((1, 1), ("data", "model")),
                 make_mesh((1,), ("data",), devices=jax.devices()[:1])):
        assert set(mesh.axis_types) == {AxisType.Auto}, mesh.axis_types


def test_run_arch_cuts_depth_only():
    arch, changed = run_arch("qwen3-8b", layers=16)
    assert changed == {"n_layers": (36, 16)}
    assert (arch.d_model, arch.n_heads, arch.d_ff, arch.vocab_size) == \
        (4096, 32, 12288, 151936)
    assert run_arch("qwen3-8b")[1] == {}
    assert run_arch("qwen3-8b", reduced=True)[0].d_model == 128
    with pytest.raises(ValueError):
        run_arch("qwen3-8b", layers=37)


class _Dev:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


def test_device_kind_table():
    assert target_for_device(_Dev("tpu", "TPU v5 lite")) == "tpu-v5e"
    for dev in (_Dev("tpu", "TPU v9 imaginary"), _Dev("cpu", "cpu")):
        with pytest.raises(KeyError):
            target_for_device(dev)


def test_serve_launcher_from_plan_end_to_end():
    arch, _ = run_arch("qwen3-8b", reduced=True)
    prompts = make_prompts(arch.vocab_size, (9, 30, 30, 17), seed=3)
    out = serve(arch, prompts, new_tokens=5, max_batch=2, max_len=64,
                mesh=make_host_mesh(), seed=3)
    assert out.plan.target == "tpu-v5e"
    assert out.engine.kv_residency == "paged"
    assert out.summary()["prefill_mode"] == "inline"
    assert sorted(r.rid for r in out.done) == [0, 1, 2, 3]
    for r in out.done:
        assert len(r.out_tokens) == 5
        assert all(0 <= t < arch.vocab_size for t in r.out_tokens)
    out.engine.shutdown()


def test_train_launcher_end_to_end(tmp_path):
    arch, _ = run_arch("hymba-1.5b", reduced=True)
    shape = ShapeConfig("launch_t", "train", 32, 2)
    tr, _, metrics = train(arch, shape, mesh=make_host_mesh(), steps=2,
                           ckpt_dir=str(tmp_path))
    losses = [h["loss"] for h in tr.history]
    assert len(losses) == 2 and all(np.isfinite(losses))
    assert abs(losses[0] - np.log(arch.vocab_size)) < 0.1 * np.log(
        arch.vocab_size)
    assert np.isfinite(float(metrics["loss"]))


def test_one_chip_plan_keeps_prefill_inline():
    """With one chip the serving process holds it: prefill workers would
    have no device, so the pass records inline and says why — even for
    the 32k shape whose modeled stall would otherwise flip to disagg."""
    plan = specialize("qwen3-8b", "decode_32k", mesh_shape=(1, 1),
                      cache=False)
    assert plan.estimates["kv_prefill_mode"] == "inline"
    assert plan.estimates["kv_prefill_stall_ticks"] > 8.0
    [entry] = [e for e in plan.log if e[1] == "kv_prefill_mode"]
    assert entry[2] == "inline" and "no device of their own" in entry[3]


def test_engine_refuses_disagg_on_tpu(monkeypatch):
    from repro.models import lm
    from repro.serve.engine import ServeEngine

    arch, _ = run_arch("qwen3-8b", reduced=True)
    plan = specialize(arch, ShapeConfig("t", "decode", 64, 2),
                      mesh_shape=(1, 1))
    params = lm.init_params(arch, jax.random.PRNGKey(0),
                            *plan.padded_sizes())
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="refused on a TPU backend"):
        ServeEngine.from_plan(plan, params, arch=arch,
                              kv_prefill_mode="disagg", disagg_workers=1)


def test_ops_never_interprets_silently():
    from repro.kernels import ops, ref

    q = jax.random.normal(jax.random.PRNGKey(0), (1, 128, 2, 64))
    with pytest.raises(RuntimeError, match="needs a TPU backend"):
        ops.flash_attention(q, q, q, mode="on")
    # auto on the CPU is the oracle, not an interpreted kernel
    np.testing.assert_array_equal(
        np.asarray(ops.flash_attention(q, q, q)),
        np.asarray(ref.flash_attention_ref(q, q, q)))


def _run(code, env, cwd=None):
    return subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, timeout=120,
                          env=env, cwd=cwd)


def test_compile_cache_env_dir_is_the_only_one(tmp_path):
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"),
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc"))
    out = _run("""
        import jax, jax.numpy as jnp
        from repro.launch.env import enable_compile_cache
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        print(enable_compile_cache())
        jax.jit(lambda x: x * 3 + 1)(jnp.ones(7)).block_until_ready()
        print(jax.config.jax_compilation_cache_dir)
    """, env)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split() == [str(tmp_path / "cc")] * 2
    assert any((tmp_path / "cc").iterdir())
    env.pop("JAX_COMPILATION_CACHE_DIR")
    out = _run("""
        import jax
        from repro.launch.env import enable_compile_cache
        print(enable_compile_cache(), jax.config.jax_compilation_cache_dir)
    """, env)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split() == [str(ROOT / ".jax_cache")] * 2


def test_chip_smoke_refuses_cpu_and_a_bare_directory(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for script in (ROOT / "chip_smoke.py",
                   shutil.copy(ROOT / "chip_smoke.py", tmp_path)):
        out = subprocess.run([sys.executable, str(script)],
                             capture_output=True, text=True, timeout=120,
                             env=env, cwd=tmp_path)
        assert out.returncode != 0, out.stdout
        assert '"ok"' not in out.stdout, out.stdout
