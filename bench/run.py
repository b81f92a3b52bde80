#!/usr/bin/env python3
"""The on-chip benchmark: one cell, one seed, one process.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is data, found by name: ``BENCHMARK.json`` at
the root lists the cells, their configuration and traffic mix, and the
metrics; ``bench/configs/<config>.json`` holds the model as it is run;
``bench/traffic/<mix>.json`` the mix's parameters, read by the one
generator ``bench/gen.py``; ``bench/cells/<cell>.json`` the cell's own
settings (the offered rate, the limits of its correctness check); and
``bench/metrics/<metric>.py`` one reader per per-layer metric.  The
cell's kind, from its mix, picks the module that drives it
(``serve`` is ``serve_cell.py``).

The run refuses a host without a TPU, or with fewer chips than the
cell asks for: it exits non-zero and prints no result.  It keeps JAX's
persistent compilation cache in the checkout (``launch/env``), makes
its weights on the device from the seed, warms every shape the cell
uses, measures for ``--seconds``, checks what the timed path produced
against the float32 reference, and prints one JSON object as the last
line of standard output.  ``--trace 1`` profiles the window and
reports the per-layer metrics instead of the end-to-end ones.  Earlier lines give the plan's decisions, the generator's
lateness and the number of XLA compiles inside the window.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_spec(workload: str, root: Path = ROOT) -> Dict[str, Any]:
    """The cell's files, and the metrics it reports.  A cell runs only
    when BENCHMARK.json lists it."""
    bench = load_json(root / "BENCHMARK.json")
    own = load_json(root / "bench" / "cells" / f"{workload}.json")
    cell = next((w for w in bench["workloads"] if w["name"] == workload),
                None)
    if cell is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    if any(cell[k] != own[k] for k in ("config", "traffic", "chips")):
        raise ValueError(f"bench/cells/{workload}.json disagrees with "
                         "BENCHMARK.json")
    config = load_json(root / "bench" / "configs" / f"{cell['config']}.json")
    mix = load_json(root / "bench" / "traffic" / f"{cell['traffic']}.json")
    mix = {**mix, **own.get("mix", {})}
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (workload in m["workloads"] if "workloads" in m
                 else m["moves"] in names)]
    return {"cell": cell, "config": config, "mix": mix, "own": own,
            "end_to_end": e2e, "per_layer": layer,
            "run_seconds": bench["run_seconds"]}


class CompileMeter:
    """XLA compiles (backend compile or persistent-cache load) seen, in
    all and inside the window."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self, jax):
        self.s = 0.0
        self.n = 0
        self.in_window = 0
        self.misses = 0
        self.window = False
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._ev)

    def _dur(self, event, secs, **_):
        if event == self.EVENT:
            self.s += secs
            self.n += 1
            self.in_window += self.window

    def _ev(self, event, **_):
        if event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


class Ctx:
    """What a cell module needs from the harness: the cell's data, the seed,
    the clock, spans and the trace."""

    def __init__(self, spec, seed: int, seconds: float, trace: bool,
                 devices, meter, scratch: Path):
        import repro.configs.base as base
        self.spec = spec
        self.config = spec["config"]
        self.mix = spec["mix"]
        self.chips = spec["cell"]["chips"]
        fields = {f.name for f in dataclasses.fields(base.ArchConfig)}
        self.arch = base.ArchConfig(**{k: v for k, v in
                                       self.config["model"].items()
                                       if k in fields})
        self.seed, self.seconds = seed, seconds
        self.devices = devices
        self.meter = meter
        self.scratch = scratch
        self.trace_dir = scratch / "trace"
        # a traced run traces its whole window: stopping the profiler
        # takes seconds, and must not fall inside it
        self.trace = trace
        self.tracing = False
        self.setup: Dict[str, float] = {}
        self._ann = None
        self._t0 = 0.0

    def line(self, msg: str) -> None:
        print(msg, flush=True)

    def span(self, name: str):
        if not self.tracing:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def begin_window(self) -> float:
        """Start the trace (when asked), open the window, return its
        start on ``time.perf_counter``."""
        import jax
        self.setup["compile_s"] = self.meter.s
        if self.trace:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            jax.profiler.start_trace(str(self.trace_dir))
            self._ann = jax.profiler.TraceAnnotation("bench.window")
            self._ann.__enter__()
            self.tracing = True
        self.meter.window = True
        self._t0 = time.perf_counter()
        self.setup["setup_s"] = self._t0 - T_START
        return self._t0

    def end_window(self) -> None:
        """Close the window, then stop the trace."""
        self.meter.window = False
        if self.tracing:
            import jax
            self._ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.tracing = False
        self.line(f"compiles inside the window: {self.meter.in_window} "
                  f"(compiles and cache loads in all: {self.meter.n}, "
                  f"{self.meter.s:.3f}s; persistent-cache misses "
                  f"{self.meter.misses})")


def cell_module(kind: str):
    return importlib.import_module(f"{kind}_cell")


def read_metric(name: str, reading) -> Optional[float]:
    if str(BENCH / "metrics") not in sys.path:
        sys.path.insert(0, str(BENCH / "metrics"))
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(reading)


class Reading:
    """What a per-layer metric reader is given."""

    def __init__(self, ctx, kind, res, trace, peaks):
        self.kind = kind
        self.ctx = ctx
        self.setup = ctx.setup
        self.res = res
        self.trace = trace
        self.peaks = peaks
        self.model = ctx.config["model"]
        self.chips = ctx.chips

    def window(self):
        """The traced window (start, end) on the trace's clock."""
        return self.trace.span("bench.window")

    def devices(self) -> List[int]:
        return sorted(self.trace.ops)[:self.chips]


def limits_of(spec) -> Dict[str, float]:
    return dict(spec["own"].get("limits", {}))


def judge(checks: Dict[str, Any], limits: Dict[str, float]):
    """(correct, {name: {value, limit}}): a number passes when it is at
    most its limit, a flag when it is true.  A number the cell sets no
    limit for is shown and not judged; a limit with no number fails."""
    out, ok = {}, True
    for k, v in checks.items():
        if isinstance(v, bool):
            out[k] = {"value": v, "limit": True}
            ok &= v
        else:
            out[k] = {"value": v, "limit": limits.get(k)}
            if k in limits:
                ok &= v <= limits[k]
    missing = [k for k in limits if k not in checks]
    return ok and not missing and bool(limits), out


def run_cell(spec, seed: int, seconds: float, trace: bool, devices,
             scratch: Path, drv=None, compile_cache: bool = True,
             control: bool = False, check: bool = True, device_lines=None,
             peaks=None) -> Dict[str, Any]:
    """Set up, measure and check one cell; returns the result object.

    ``control`` also judges the control (the reference in fp8 in the
    program's place) under the cell's limits, as ``out["control"]``;
    ``check=False`` skips the comparison (a rate sweep).  Both are for
    ``calibrate.py``.  ``device_lines`` and ``peaks`` let a test reduce
    a trace taken on the CPU."""
    import jax
    from repro.launch.env import enable_compile_cache

    cache = "off"
    if compile_cache:
        cache = enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    meter = CompileMeter(jax)
    ctx = Ctx(spec, seed, seconds, trace, devices, meter, scratch)
    ctx.line(f"cell {spec['cell']['name']} seed {seed} seconds {seconds} "
             f"trace {int(trace)} device {devices[0].device_kind} "
             f"x{len(devices)} compile cache {cache}")
    kind = spec["mix"]["kind"]
    drv = drv or cell_module(kind)
    st = drv.setup(ctx)
    res = drv.window(ctx, st)
    used = devices[:ctx.chips]
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in used)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    metrics: Dict[str, Any] = {}
    units = {m["name"]: m["unit"] for m in
             spec["end_to_end"] + spec["per_layer"]}
    breakdown = None
    if trace:
        import devtrace
        peaks = peaks or load_json(BENCH / "peaks.json")["kinds"][
            devices[0].device_kind]
        tr = devtrace.load(devtrace.find_xplane(str(ctx.trace_dir)),
                           device_lines or devtrace.tpu_lines)
        rd = Reading(ctx, kind, res, tr, peaks)
        lo, hi = rd.window()
        devs = rd.devices()
        device["busy_s"] = sum(devtrace.busy(tr.ops[d], lo, hi)
                               for d in devs) / len(devs)
        device["window_s"] = hi - lo
        for m in spec["per_layer"]:
            v = read_metric(m["name"], rd)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        ops: Dict[str, float] = {}
        for d in devs:
            for k, v in devtrace.by_name(tr.ops[d], lo, hi).items():
                ops[k] = ops.get(k, 0.0) + v / len(devs)
        breakdown = {"device_ops": devtrace.top(ops),
                     "idle_gaps": devtrace.attribute(
                         devtrace.gaps(tr.ops[devs[0]], lo, hi), tr.spans)}
    else:
        vals = {**drv.end_to_end(ctx, res), "setup_s": ctx.setup["setup_s"]}
        for m in spec["end_to_end"]:
            metrics[m["name"]] = {"value": float(vals[m["name"]]),
                                  "unit": units[m["name"]]}
    out = {"correct": False,
           "attempted": int(res.get("attempted", 0)),
           "failed": int(res.get("n_failed", 0)),
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    if not check:
        import gc
        st.clear()
        gc.collect()
        return out
    checks = drv.check(ctx, st, res, control=control)
    if control:
        c_ok, c_shown = judge(checks["control"], limits_of(spec))
        out["control"] = {"correct": bool(c_ok), "checks": c_shown}
    out["correct"], out["checks"] = judge(checks["program"],
                                          limits_of(spec))
    out["correct"] = bool(out["correct"])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: no program beside {BENCH} (src/repro missing)",
              file=sys.stderr)
        return 2
    try:
        spec = load_spec(args.workload)
    except (KeyError, OSError, StopIteration) as e:
        print(f"bench: cannot load cell {args.workload!r}: {e}",
              file=sys.stderr)
        return 2
    scratch = ROOT / ".bench"
    scratch.mkdir(exist_ok=True)
    os.environ["REPRO_PLAN_DIR"] = str(scratch / "plans")
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < spec["cell"]["chips"]:
        print(f"bench: cell {args.workload} needs {spec['cell']['chips']} "
              f"TPU chip(s); JAX found {len(devices)} "
              f"{devices[0].platform} device(s)", file=sys.stderr)
        return 1
    try:
        out = run_cell(spec, args.seed, args.seconds, bool(args.trace),
                       devices, scratch)
    except Exception:
        traceback.print_exc()
        return 1
    for k, v in out["checks"].items():
        print(f"check {k} {v['value']} limit {v['limit']}", file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
