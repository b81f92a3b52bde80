"""Serving launcher: specialize a decode plan, serve batched requests.

    python -m repro.launch.serve [--arch qwen3-8b] [--layers N] [--reduced]
        [--requests 8] [--max-batch 4] [--max-len 128] [--new-tokens 16]
        [--mesh DxM] [--coordinator host:port --num-processes P
        --process-id I] [--disagg N] [--seed S]

The main path is :func:`serve`: ``specialize`` for a decode shape →
``init_params`` → :meth:`ServeEngine.from_plan` on a mesh → submit →
``run_until_idle``, through whatever decode implementation the plan
chose.  ``chip_smoke.py`` calls the same function.  The published
config is the default; ``--layers`` cuts depth only, ``--reduced``
swaps in the tiny CPU config.  ``--mesh DxM`` lays the devices out as
(data, model); ``--coordinator`` enables multi-host serving via
``jax.distributed.initialize`` (every process runs the same command
with its own ``--process-id``).  ``--from-plan`` is accepted for older
command lines: plan mode is the only mode.
"""

from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Any, Dict, List, Sequence

import jax
import numpy as np


@dataclasses.dataclass
class Served:
    """What one :func:`serve` call built and returned."""

    plan: Any                  # FrozenPlan
    engine: Any                # ServeEngine
    done: List[Any]            # finished Requests, in finish order
    serve_s: float             # submit -> idle, wall (compiles included)

    def summary(self) -> Dict[str, Any]:
        """Plan and engine decisions a run reports."""
        eng = self.engine
        return {"plan": self.plan.content_hash()[:12],
                "decode_impl": self.plan.estimates.get("decode_impl", "xla"),
                "decode_path": eng.decode_path,
                "kv_residency": eng.kv_residency,
                "prefill_mode": eng.prefill_mode,
                "kv_blocks": eng.n_blocks,
                "kv_block_len": eng.block_len}


def make_prompts(vocab: int, lens: Sequence[int], seed: int
                 ) -> List[np.ndarray]:
    """Seeded random prompts of the given lengths."""
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, (n,)).astype(np.int32) for n in lens]


def serve(arch, prompts: Sequence[np.ndarray], *, new_tokens: int,
          max_batch: int, max_len: int, mesh, seed: int = 0,
          disagg: int = 0, plan=None, engine_cls=None) -> Served:
    """specialize (for the present device) → init_params →
    ServeEngine.from_plan → submit → run_until_idle.

    ``plan`` serves an existing plan instead (one specialized for
    another mesh, so two meshes serve identical weights);
    ``engine_cls`` substitutes a ServeEngine subclass (a probe that
    records what it samples)."""
    from repro.configs import ShapeConfig
    from repro.core.pipeline import specialize
    from repro.launch.env import plan_target
    from repro.models import init_params
    from repro.serve.engine import ServeEngine

    shape = ShapeConfig("serve", "decode", max_len, max_batch)
    plan = plan or specialize(arch, shape,
                              mesh_axes=tuple(mesh.axis_names),
                              mesh_shape=tuple(mesh.devices.shape),
                              target=plan_target())
    # one jitted init: the RNG fuses into each leaf, no fp32 temporaries
    params = jax.jit(lambda k: init_params(arch, k, *plan.padded_sizes()))(
        jax.random.PRNGKey(seed))
    engine = (engine_cls or ServeEngine).from_plan(
        plan, params, arch=arch, mesh=mesh, seed=seed,
        kv_prefill_mode="disagg" if disagg else None,
        disagg_workers=disagg)
    t0 = time.perf_counter()
    for p in prompts:
        engine.submit(p, max_new_tokens=new_tokens)
    # disagg ticks mostly sleep while workers compile/prefill off-process;
    # give them a far larger budget than inline's deadlock watchdog
    done = engine.run_until_idle(60000 if disagg else 1000)
    return Served(plan, engine, done, time.perf_counter() - t0)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b")
    ap.add_argument("--layers", type=int, default=0,
                    help="depth cut: run the first N layers (0 = all)")
    ap.add_argument("--reduced", action="store_true",
                    help="tiny same-family config for CPU runs")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--from-plan", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--mesh", default="",
                    help='"DxM" (data, model) mesh over the visible '
                         "devices, e.g. 1x2 (default: all devices x 1)")
    ap.add_argument("--coordinator", default="",
                    help="host:port for jax.distributed.initialize "
                         "(multi-host serving)")
    ap.add_argument("--num-processes", type=int, default=1)
    ap.add_argument("--process-id", type=int, default=0)
    ap.add_argument("--disagg", type=int, default=0, metavar="N",
                    help="run prefill on N supervised worker processes "
                         "(disaggregated prefill/decode; CPU only)")
    args = ap.parse_args()

    if args.coordinator:
        jax.distributed.initialize(
            coordinator_address=args.coordinator,
            num_processes=args.num_processes, process_id=args.process_id)

    from repro.configs import run_arch
    from repro.launch.env import enable_compile_cache
    from repro.launch.mesh import make_mesh

    enable_compile_cache()
    arch, changed = run_arch(args.arch, reduced=args.reduced,
                             layers=args.layers)
    print(f"arch {arch.name} changed from published: "
          + (", ".join(f"{k} {a}->{b}" for k, (a, b) in changed.items())
             or "none"))
    if args.mesh:
        d, m = (int(x) for x in args.mesh.lower().split("x"))
    else:
        d, m = len(jax.devices()), 1
    rng = np.random.default_rng(args.seed)
    lens = rng.integers(8, args.max_len - args.new_tokens - 1,
                        args.requests)
    out = serve(arch, make_prompts(arch.vocab_size, lens, args.seed),
                new_tokens=args.new_tokens, max_batch=args.max_batch,
                max_len=args.max_len, mesh=make_mesh((d, m),
                                                     ("data", "model")),
                seed=args.seed, disagg=args.disagg)
    print(" ".join(f"{k}={v}" for k, v in out.summary().items())
          + f" on mesh {d}x{m}")
    toks = sum(len(r.out_tokens) for r in out.done)
    print(f"served {len(out.done)} requests, {toks} tokens in "
          f"{out.serve_s:.2f}s wall (compiles included)")
    for r in out.done[:3]:
        print(f"  rid={r.rid} tokens={r.out_tokens[:8]}...")
    out.engine.shutdown()


if __name__ == "__main__":
    main()
