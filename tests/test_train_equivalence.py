"""Trajectory equivalence of the lowered int8+EF wire train step.

The tentpole claim of the lowered compression path is NOT "the loss is
close after one step" — it is that the *trajectory* of the compressed
run tracks the fp32 baseline across steps, because error feedback
telescopes: with per-slice residual ``e_i`` and delivered mean
``ghat_t = mean_i Q(g_i_t + e_i_t)``,

    sum_t ghat_t + mean_i e_i_T == sum_t mean_i g_i_t        (exactly)

so the cumulative delivered gradient differs from the cumulative true
gradient by ONE bounded residual (<= half a quantization step per
element), not by anything that grows with T.  Per-step loss divergence
is then bounded by the optimizer's sensitivity to that bounded kick —
small, and crucially not compounding.

These tests run the REAL lowered step (Trainer -> lower_train_step) on a
2x4 host-device mesh in a subprocess, and prove the wire claim on the
compiled HLO: with compression lowered there is no gradient-sized float
all-reduce in the step — the only big cross-data collectives are int16
code sums.
"""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax.numpy as jnp
import numpy as np

SRC = str(Path(__file__).resolve().parents[1] / "src")


def run_subprocess(code: str, timeout: int = 900) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-c",
         "from repro.launch.mesh import make_mesh\n" + textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_wire_trajectory_tracks_fp32_and_no_float_reduce_in_hlo():
    """int8+EF trajectory vs fp32 baseline over 4 steps on a 2x4 mesh,
    plus the wire proof: zero gradient-sized f32/bf16 reductions and >=1
    int16 all-reduce in the compiled compressed step."""
    run_subprocess("""
        import re
        import numpy as np
        import jax
        from collections import Counter
        from repro.configs import ShapeConfig, get_arch
        from repro.core.pipeline import specialize
        from repro.models import synthetic_batch
        from repro.optim.adamw import OptConfig
        from repro.train.trainer import Trainer, TrainerConfig

        arch = get_arch("qwen3-8b").reduced()
        shape = ShapeConfig("wire_eq", "train", 64, 8)
        mesh = make_mesh((2, 4), ("data", "model"))

        def run(gc):
            plan = specialize(arch, shape, mesh_axes=("data", "model"),
                              mesh_shape=(2, 4), cache=False,
                              grad_compression=gc)
            tr = Trainer(plan, mesh, TrainerConfig(n_steps=1, ckpt_every=0),
                         opt_cfg=OptConfig(total_steps=8),
                         arch=arch, shape=shape)
            state = tr.init_state()
            losses, gnorms = [], []
            for i in range(4):
                b = synthetic_batch(arch, shape, jax.random.PRNGKey(100 + i))
                state, m = tr.step_fn(state, b)
                losses.append(float(m["loss"]))
                gnorms.append(float(m["grad_norm"]))
            return plan, tr, state, losses, gnorms

        plan_on, tr_on, st_on, l_on, g_on = run("on")
        assert plan_on.comm.compress_grads and plan_on.comm.compress_lowered
        assert plan_on.estimates["grad_compress_lowered"] == 2.0  # dp

        # EF residuals live per DP slice: leading (dp,) axis, bf16
        for leaf in jax.tree.leaves(st_on["opt"]["ef"]):
            assert leaf.shape[0] == 2 and leaf.dtype == jax.numpy.bfloat16

        plan_off, tr_off, st_off, l_off, g_off = run("off")
        assert not plan_off.comm.compress_grads
        assert "grad_compress_lowered" not in plan_off.estimates

        # step 0's forward sees identical weights -> identical loss;
        # later steps track within the telescoping bound (measured
        # ~5e-5 on host CPU; 1e-3 pins the order of magnitude without
        # platform brittleness)
        assert l_on[0] == l_off[0], (l_on[0], l_off[0])
        for t, (a, b) in enumerate(zip(l_on, l_off)):
            assert abs(a - b) < 1e-3, (t, a, b)
        # grad norms: quantization perturbs but does not distort scale
        for t, (a, b) in enumerate(zip(g_on, g_off)):
            assert abs(a - b) / max(abs(b), 1e-9) < 0.05, (t, a, b)

        # ---- the wire proof on compiled HLO -------------------------
        # Replica groups, not element counts: on the reduced arch every
        # collective tops out at 16384 elements, and the megatron
        # model-axis activation reduces are shipped identically by both
        # steps — only collectives whose groups span the DATA axis
        # ({{0,4},{1,5},...} literal / [4,2]<=[2,4] iota on this (2,4)
        # mesh) are the gradient wire. "Gradient-sized" = >= 4096
        # elements; the surviving small cross-data floats are shared
        # quantizer scales and loss/grad-norm scalars.  XLA's combiner
        # may fuse several reduces into one tuple-shaped all-reduce:
        # each tuple element counts as its own collective.
        b = synthetic_batch(arch, shape, jax.random.PRNGKey(100))

        def xdata_counts(tr, state):
            txt = tr.step_fn.lower(state, b).compile().as_text()
            c = Counter()
            for line in txt.splitlines():
                m = re.search(
                    r"= (\\(.*?\\)|\\w+\\[[\\d,]*\\]\\S*) (all-reduce|"
                    r"reduce-scatter)\\(", line)
                if m is None:
                    continue
                if not ("replica_groups={{0,4}" in line
                        or "replica_groups=[4,2]<=[2,4]" in line):
                    continue
                for dt, dims in re.findall(r"(\\w+)\\[([\\d,]*)\\]",
                                           m.group(1)):
                    n = int(np.prod([int(t) for t in dims.split(",")
                                     if t] or [1]))
                    c[dt, n >= 4096] += 1
            return c

        on = xdata_counts(tr_on, st_on)
        off = xdata_counts(tr_off, st_off)
        # the baseline ships gradients as big cross-data float reduces
        # (proves the classifier actually sees the wire) ...
        assert off["f32", True] >= 1, off
        assert off["s16", True] == 0, off
        # ... and the compressed step ships ZERO — its only big
        # cross-data collectives are the int16 code sums
        assert on["f32", True] == 0 and on["bf16", True] == 0, on
        assert on["s16", True] >= 1, "no int16 code-sum all-reduce found"
        print("OK")
    """)


def test_compress_off_is_bit_deterministic():
    """Regression pin: the uncompressed step is bit-deterministic —
    two independent runs from the same seed produce identical losses
    (so any future trajectory drift is attributable to the wire path,
    not ambient nondeterminism)."""
    run_subprocess("""
        import jax
        from repro.configs import ShapeConfig, get_arch
        from repro.core.pipeline import specialize
        from repro.models import synthetic_batch
        from repro.optim.adamw import OptConfig
        from repro.train.trainer import Trainer, TrainerConfig

        arch = get_arch("qwen3-8b").reduced()
        shape = ShapeConfig("wire_det", "train", 64, 8)
        mesh = make_mesh((2, 4), ("data", "model"))
        plan = specialize(arch, shape, mesh_axes=("data", "model"),
                          mesh_shape=(2, 4), cache=False,
                          grad_compression="off")

        def run():
            tr = Trainer(plan, mesh, TrainerConfig(n_steps=1, ckpt_every=0),
                         opt_cfg=OptConfig(total_steps=8),
                         arch=arch, shape=shape)
            state = tr.init_state()
            out = []
            for i in range(3):
                b = synthetic_batch(arch, shape, jax.random.PRNGKey(7 + i))
                state, m = tr.step_fn(state, b)
                out.append(float(m["loss"]))
            return out

        a, b = run(), run()
        assert a == b, (a, b)
        print("OK")
    """)


# ---------------------------------------------------------------------
# unit-level telescoping identities (no mesh needed)
# ---------------------------------------------------------------------

def test_slice_sum_telescoping_identity_exact():
    """mean + mean_i(err_i) == mean_i(x_i) to f32 rounding, per element."""
    from repro.dist.collectives import compressed_slice_sum
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((4, 3, 200)), jnp.float32)
    mean, err = compressed_slice_sum(x)
    lhs = np.asarray(mean + jnp.mean(err, axis=0))
    rhs = np.asarray(jnp.mean(x, axis=0))
    assert np.abs(lhs - rhs).max() < 1e-6


def test_ef_residual_bounded_on_constant_gradients():
    """Constant per-slice gradients: the cumulative delivered mean
    converges to the true mean at rate bound/T (the residual never
    drains below the quantization floor, but never grows either)."""
    from repro.dist.collectives import compressed_slice_sum
    rng = np.random.default_rng(1)
    g = jnp.asarray(rng.standard_normal((2, 257)) * 0.01 + 1.3, jnp.float32)
    err = jnp.zeros_like(g)
    total = jnp.zeros(g.shape[1:], jnp.float32)
    T = 16
    step = float(jnp.abs(g).max()) / 127.0       # quantization step bound
    for _ in range(T):
        mean, err = compressed_slice_sum(g + err)
        total = total + mean
    true = np.asarray(jnp.mean(g, axis=0))
    # telescoping: |total/T - true| == |mean residual| / T <= step/2/T
    # (2% slack: the shared scale quantizes acc = g + err, whose amax
    # can exceed g's by up to half a step)
    gap = np.abs(np.asarray(total) / T - true).max()
    assert gap <= step / 2 / T * 1.02 + 1e-7, (gap, step / 2 / T)
    # and the residual itself stays at the quantization floor
    assert float(jnp.abs(err).max()) <= step / 2 * 1.02
