#!/usr/bin/env python3
"""Readings that a cell's correctness limits and offered rate are set from.

    python bench/calibrate.py --workload <cell> --seeds 11,12,... \\
        --seconds <s> [--control 3] [--rates 0.6,0.8,1.0]

Runs the cell once per seed in this one process, exactly as
``run.py`` does, and prints each run's compared numbers (one JSON line
per seed).  The first ``--control`` seeds also judge the control under
the cell's limits: the float32 reference computed in fp8 put in the
program's place (for serving, at each served position the token that
the fp8 reference puts first); it has to come out not correct.  The
last line sums up the largest program reading and the smallest control
reading of each number.

``--rates`` is the sweep that finds the highest rate the system
sustains: it runs each seed at each offered rate in place of the
cell's, with no check; the window lines say whether the queue grew.
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import sys

import run


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--rates", default="")
    args = ap.parse_args()
    spec = run.load_spec(args.workload)
    scratch = run.ROOT / ".bench"
    scratch.mkdir(exist_ok=True)
    os.environ["REPRO_PLAN_DIR"] = str(scratch / "plans")
    sys.path.insert(0, str(run.ROOT / "src"))
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < spec["cell"]["chips"]:
        print("calibrate: no TPU with enough chips", file=sys.stderr)
        return 1
    seeds = [int(s) for s in args.seeds.split(",")]
    if args.rates:
        for rate in (float(x) for x in args.rates.split(",")):
            sp = copy.deepcopy(spec)
            sp["mix"]["rate_rps"] = rate
            for seed in seeds:
                out = run.run_cell(sp, seed, args.seconds, False, devices,
                                   scratch, check=False)
                print(json.dumps({"rate": rate, "seed": seed,
                                  "attempted": out["attempted"],
                                  "metrics": {k: v["value"] for k, v in
                                              out["metrics"].items()}}),
                      flush=True)
        return 0
    prog, ctrl = {}, {}
    for i, seed in enumerate(seeds):
        out = run.run_cell(spec, seed, args.seconds, False, devices,
                           scratch, control=i < args.control)
        vals = {k: v["value"] for k, v in out["checks"].items()}
        line = {"seed": seed, "correct": out["correct"],
                "attempted": out["attempted"], "failed": out["failed"],
                "checks": vals,
                "metrics": {k: v["value"] for k, v in out["metrics"].items()}}
        for k, v in vals.items():
            if not isinstance(v, bool):
                prog[k] = max(prog.get(k, v), v)
        if "control" in out:
            cv = {k: v["value"] for k, v in out["control"]["checks"].items()}
            line["control"] = {"correct": out["control"]["correct"],
                               "checks": cv}
            for k, v in cv.items():
                if not isinstance(v, bool):
                    ctrl[k] = min(ctrl.get(k, v), v)
        print(json.dumps(line), flush=True)
    print(json.dumps({"program_max": prog, "control_min": ctrl}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
