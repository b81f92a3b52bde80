"""Shared by the readers of the held-expert layer's counter: the
engine's ``serve.moe`` marks (one per sampled program, its ``pairs``
token-expert pairs on held experts and its ``phase``) on the trace's
clock, summed per traced tick."""

import bisect

import devtrace


def marks(r):
    """[(time, phase, pairs)] of the engine's ``serve.moe`` marks, by
    time; empty when the program makes none."""
    got = getattr(r, "moe_marks", None)
    if got is None:
        got = r.moe_marks = read(devtrace.find_xplane(str(r.ctx.trace_dir)))
    return got


def read(path):
    """The ``serve.moe`` marks of the host planes of the trace file at
    ``path``.  A mark's arguments are its event's stats, or (where the
    profiler keeps them in the name) ``serve.moe#phase=...,pairs=...#``."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                name, _, meta = ev.name.partition("#")
                if name != "serve.moe":
                    continue
                args = {k: str(v) for k, v in ev.stats}
                args.update(kv.split("=", 1) for kv in meta.split(",")
                            if "=" in kv)
                out.append((ev.start_ns * 1e-9, args.get("phase", ""),
                            int(args.get("pairs", 0))))
    return sorted(out)


def per_tick(r, ticks):
    """{phase: pairs} of each tick in ``ticks`` (the ``res["ticks"]``
    entries): the marks that fall inside it."""
    got = marks(r)
    times = [t for t, _, _ in got]
    lo, _ = r.window()
    out = []
    for t in ticks:
        a = bisect.bisect_left(times, lo + t["start"])
        b = bisect.bisect_right(times, lo + t["end"])
        d = {}
        for _, phase, n in got[a:b]:
            d[phase] = d.get(phase, 0) + n
        out.append(d)
    return out
