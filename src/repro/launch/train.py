"""Training launcher: specialize a train shape, then ``Trainer.fit``.

    python -m repro.launch.train [--arch qwen3-8b] [--layers N] [--reduced]
        [--steps 20] [--seq-len 128] [--batch 4] [--model-parallel 1]
        [--ckpt-dir DIR] [--ckpt-every K] [--seed S]

The main path is :func:`train`: ``specialize`` (the paper's compilation
passes) → lower ("HLS") → ``Trainer.fit`` with checkpointing on a mesh
over this process's devices.  ``chip_smoke.py`` calls the same function.
The published config is the default; ``--layers`` cuts depth only,
``--reduced`` swaps in the tiny CPU config.
"""

from __future__ import annotations

import argparse
import time
from typing import Any, Dict, Tuple

import jax


def train(arch, shape, *, mesh, steps: int, ckpt_dir: str,
          ckpt_every: int = 0, seed: int = 0, log_every: int = 0,
          **options) -> Tuple[Any, Any, Dict[str, Any]]:
    """specialize (for the present device) → Trainer.fit; returns
    ``(trainer, state, metrics)``.  ``options`` go to ``specialize``
    (e.g. ``grad_compression``)."""
    from repro.core.pipeline import specialize
    from repro.launch.env import plan_target
    from repro.train.trainer import Trainer, TrainerConfig

    plan = specialize(arch, shape, mesh_axes=tuple(mesh.axis_names),
                      mesh_shape=tuple(mesh.devices.shape),
                      target=plan_target(), **options)
    trainer = Trainer(plan, mesh, TrainerConfig(
        n_steps=steps, ckpt_every=ckpt_every, ckpt_dir=ckpt_dir,
        log_every=log_every or max(steps // 10, 1), seed=seed),
        arch=arch, shape=shape)
    state, metrics = trainer.fit()
    return trainer, state, metrics


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b")
    ap.add_argument("--layers", type=int, default=0,
                    help="depth cut: run the first N layers (0 = all)")
    ap.add_argument("--reduced", action="store_true",
                    help="tiny same-family config for CPU runs")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="ckpt")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from repro.configs import ShapeConfig, run_arch
    from repro.launch.env import enable_compile_cache
    from repro.launch.mesh import make_host_mesh

    enable_compile_cache()
    arch, changed = run_arch(args.arch, reduced=args.reduced,
                             layers=args.layers)
    print(f"arch {arch.name} changed from published: "
          + (", ".join(f"{k} {a}->{b}" for k, (a, b) in changed.items())
             or "none"))
    shape = ShapeConfig("cli", "train", args.seq_len, args.batch)
    t0 = time.perf_counter()
    trainer, _, metrics = train(
        arch, shape, mesh=make_host_mesh(model=args.model_parallel),
        steps=args.steps, ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every, seed=args.seed)
    print("plan decisions:")
    for entry in trainer.plan.log:
        print("  ", " | ".join(entry))
    print("final:", {k: float(v) for k, v in metrics.items()},
          f"in {time.perf_counter() - t0:.1f}s wall (compiles included)")


if __name__ == "__main__":
    main()
