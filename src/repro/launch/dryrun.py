import os
os.environ["XLA_FLAGS"] = os.environ.get("DRYRUN_XLA_FLAGS",
    "--xla_force_host_platform_device_count=512")

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

MUST be invoked as its own process (``python -m repro.launch.dryrun``):
the first two lines above pin 512 placeholder devices BEFORE any jax
initialization.  Nothing here allocates device memory — inputs are
ShapeDtypeStructs, and compile artifacts are analyzed, not executed.

Per cell it records to results/dryrun/<arch>@<shape>@<mesh>.json:
  * memory_analysis()   (bytes-per-device: proves the plan fits HBM)
  * cost_analysis()     (raw XLA FLOPs/bytes)
  * trip-count-corrected FLOPs / HBM bytes / collective bytes
  * the three roofline terms + bottleneck (single-pod mesh)
  * the specialization plan's decision log

Usage:
  python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--skip-done]
"""

import argparse
import dataclasses
import json
import sys
import time
import traceback
from pathlib import Path

RESULTS = Path(__file__).resolve().parents[3] / "results" / "dryrun"


def run_cell(arch_name: str, shape_name: str, multi_pod: bool,
             overrides: dict | None = None, tag: str = "") -> dict:
    import jax
    from repro.analysis import roofline
    from repro.configs import applicable, get_arch, get_shape
    from repro.core.passes.lowering import lower_step
    from repro.core.pipeline import specialize
    from repro.launch.mesh import make_production_mesh

    arch = get_arch(arch_name)
    shape = get_shape(shape_name)
    ok, why = applicable(arch, shape)
    mesh_desc = "2x16x16" if multi_pod else "16x16"
    out = {
        "arch": arch_name, "shape": shape_name, "mesh": mesh_desc,
        "runnable": ok, "skip_reason": why, "timestamp": time.time(),
    }
    if not ok:
        return out

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    plan = specialize(
        arch, shape,
        mesh_axes=tuple(mesh.axis_names),
        mesh_shape=tuple(mesh.devices.shape),
        **(overrides or {}),
    )
    step = lower_step(plan, mesh)
    lowered = step.lower()
    t_lower = time.time() - t0

    t1 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t1

    mem = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    hlo = compiled.as_text()

    rep = roofline.analyze(
        arch=arch, shape=shape, kind=step.kind, hlo_text=hlo,
        n_devices=mesh.devices.size, cost_analysis=ca, memory_stats=mem,
        mesh_desc=mesh_desc, target=plan.target,
    )
    out.update(
        kind=step.kind,
        lower_s=round(t_lower, 2),
        compile_s=round(t_compile, 2),
        memory_analysis={
            "argument_size": mem.argument_size_in_bytes,
            "output_size": mem.output_size_in_bytes,
            "temp_size": mem.temp_size_in_bytes,
            "alias_size": mem.alias_size_in_bytes,
            "peak_estimate_per_device": (
                mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes - mem.alias_size_in_bytes),
        },
        cost_analysis={k: float(v) for k, v in ca.items()
                       if k in ("flops", "bytes accessed")},
        roofline=rep.to_json(),
        plan_log=[list(e) for e in plan.log],
        plan_estimates=dict(plan.estimates),
        plan_opt=dict(plan.opt),
        plan_hash=plan.content_hash(),
        hlo_sizes={"n_lines": hlo.count(chr(10))},
    )
    return out


def cell_path(arch: str, shape: str, mesh_desc: str, tag: str = "") -> Path:
    sfx = f"@{tag}" if tag else ""
    return RESULTS / f"{arch}@{shape}@{mesh_desc}{sfx}.json"


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--skip-done", action="store_true")
    ap.add_argument("--tag", default="")
    ap.add_argument("--overrides", default="",
                    help="JSON dict forwarded to specialize() (perf iters)")
    args = ap.parse_args()

    from repro.configs import all_cells  # late import (after XLA_FLAGS)

    RESULTS.mkdir(parents=True, exist_ok=True)
    overrides = json.loads(args.overrides) if args.overrides else None
    mesh_desc = "2x16x16" if args.multi_pod else "16x16"

    if args.all:
        cells = [(a, s) for a, s, ok, _ in all_cells()]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    failures = 0
    for a, s in cells:
        path = cell_path(a, s, mesh_desc, args.tag)
        if args.skip_done and path.exists():
            print(f"[skip] {a}@{s}@{mesh_desc}")
            continue
        print(f"[cell] {a}@{s}@{mesh_desc} ...", flush=True)
        try:
            out = run_cell(a, s, args.multi_pod, overrides, args.tag)
        except Exception as e:  # noqa: BLE001 - record and continue
            out = {"arch": a, "shape": s, "mesh": mesh_desc,
                   "error": f"{type(e).__name__}: {e}",
                   "traceback": traceback.format_exc()[-4000:]}
            failures += 1
            print(f"  FAILED: {e}", flush=True)
        path.write_text(json.dumps(out, indent=2, default=str))
        if "roofline" in out:
            r = out["roofline"]
            print(f"  ok kind={out['kind']} compile={out['compile_s']}s "
                  f"bottleneck={r['bottleneck']} "
                  f"step={r['step_time_s']*1e3:.1f}ms mfu={r['mfu']:.3f} "
                  f"mem/dev={out['memory_analysis']['peak_estimate_per_device']/2**30:.2f}GiB",
                  flush=True)
        elif out.get("skip_reason"):
            print(f"  skipped: {out['skip_reason']}", flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
