"""Reduce a profiler trace to numbers: device busy and idle time, device
time per program and per operation, collective time that no compute
hides, and the longest idle gaps with the host span they fell in.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, through
``jax.profiler.ProfileData`` and nothing else.  On a TPU each chip is a
plane ``/device:TPU:<n>``; its line ``XLA Ops`` holds every operation
the chip ran and ``XLA Modules`` every program execution.  Host spans
are the benchmark's own ``TraceAnnotation``s, named ``bench.*``.  All
times are on one clock, in seconds.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Callable, Dict, List, Optional, Sequence, Tuple

Interval = Tuple[str, float, float]          # (name, start s, end s)

COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|all-to-all|"
                        r"collective-permute|allreduce|allgather")
_TPU = re.compile(r"^/device:TPU:(\d+)$")


@dataclasses.dataclass
class Trace:
    ops: Dict[int, List[Interval]]           # device -> operations
    modules: Dict[int, List[Interval]]       # device -> program runs
    spans: List[Interval]                    # host spans, by start

    def span(self, name: str) -> Optional[Tuple[float, float]]:
        """First span of ``name`` as (start, end)."""
        for n, s, e in self.spans:
            if n == name:
                return s, e
        return None


def tpu_lines(plane: str, line: str) -> Optional[Tuple[int, str]]:
    """(device, "ops" | "modules") for a TPU plane's op and module lines."""
    m = _TPU.match(plane)
    if not m:
        return None
    kind = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(line)
    return (int(m.group(1)), kind) if kind else None


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load(path: str, device_lines: Callable = tpu_lines,
         span_prefix: str = "bench.") -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: Dict[int, List[Interval]] = defaultdict(list)
    mods: Dict[int, List[Interval]] = defaultdict(list)
    spans: List[Interval] = []
    for plane in pd.planes:
        for line in plane.lines:
            dev = device_lines(plane.name, line.name)
            if dev is None and not plane.name.startswith("/host"):
                continue
            for ev in line.events:
                s = ev.start_ns * 1e-9
                e = s + ev.duration_ns * 1e-9
                if dev is not None:
                    # an op's name is its HLO instruction; keep the name
                    (ops if dev[1] == "ops" else mods)[dev[0]].append(
                        (ev.name.split(" = ", 1)[0], s, e))
                elif ev.name.startswith(span_prefix):
                    spans.append((ev.name, s, e))
    for d in (ops, mods):
        for v in d.values():
            v.sort(key=lambda x: x[1])
    spans.sort(key=lambda x: x[1])
    return Trace(dict(ops), dict(mods), spans)


def merge(intervals: Sequence[Interval], lo: float = float("-inf"),
          hi: float = float("inf")) -> List[Tuple[float, float]]:
    """Union of intervals clipped to [lo, hi], as sorted disjoint pairs."""
    out: List[List[float]] = []
    for _, s, e in sorted(intervals, key=lambda x: x[1]):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_between(merged: Sequence[Tuple[float, float]], a: float,
                 b: float) -> float:
    """Seconds of [a, b] covered by ``merged`` (the output of
    :func:`merge`)."""
    import bisect
    i = max(bisect.bisect_right([s for s, _ in merged], a) - 1, 0)
    total = 0.0
    while i < len(merged) and merged[i][0] < b:
        s, e = merged[i]
        total += max(0.0, min(e, b) - max(s, a))
        i += 1
    return total


def busy(intervals: Sequence[Interval], lo: float, hi: float) -> float:
    """Seconds of [lo, hi] in which some interval runs."""
    return sum(e - s for s, e in merge(intervals, lo, hi))


def gaps(intervals: Sequence[Interval], lo: float,
         hi: float) -> List[Tuple[float, float]]:
    """Idle stretches of [lo, hi], longest first."""
    out, t = [], lo
    for s, e in merge(intervals, lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return sorted(out, key=lambda g: g[0] - g[1])


def by_name(intervals: Sequence[Interval], lo: float = float("-inf"),
            hi: float = float("inf")) -> Dict[str, float]:
    """Seconds per name of the intervals that start in [lo, hi)."""
    out: Dict[str, float] = defaultdict(float)
    for n, s, e in intervals:
        if lo <= s < hi:
            out[n] += e - s
    return dict(out)


def within(intervals: Sequence[Interval], lo: float,
           hi: float) -> List[Interval]:
    """Intervals that start in [lo, hi)."""
    return [iv for iv in intervals if lo <= iv[1] < hi]


def exposed_collectives(ops: Sequence[Interval], lo: float,
                        hi: float) -> float:
    """Seconds of [lo, hi] in which a collective runs and no other
    operation does."""
    coll = [iv for iv in ops if COLLECTIVE.search(iv[0])]
    rest = merge([iv for iv in ops if not COLLECTIVE.search(iv[0])], lo, hi)
    total = 0.0
    for s, e in merge(coll, lo, hi):
        covered = sum(max(0.0, min(e, b) - max(s, a)) for a, b in rest)
        total += (e - s) - covered
    return total


def attribute(gap_list: Sequence[Tuple[float, float]],
              spans: Sequence[Interval], n: int = 10) -> List[list]:
    """The ``n`` longest gaps as [[host span, seconds]], the span being
    the innermost ``bench.*`` span covering the gap's middle (``idle``
    when none does)."""
    out = []
    for s, e in gap_list[:n]:
        mid = (s + e) / 2
        inner = [sp for sp in spans if sp[1] <= mid <= sp[2]]
        name = min(inner, key=lambda sp: sp[2] - sp[1])[0] if inner \
            else "idle"
        out.append([name, e - s])
    return out


def top(totals: Dict[str, float], n: int = 10) -> List[list]:
    return [[k, v] for k, v in sorted(totals.items(),
                                      key=lambda kv: -kv[1])[:n]]
