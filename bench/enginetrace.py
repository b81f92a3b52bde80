"""What the serving engine leaves in a profiler trace, beside what
``devtrace.load`` reads: its ``serve.*`` spans (``repro/runtime/trace``)
and, for each device operation, the program it ran in and its scope
path (the ``jax.named_scope`` names of its HLO metadata).

An operation's program is the program run on its device that covers
it (``jit_serve_decode(<id>)`` on a TPU's ``XLA Modules`` line), or
the ``hlo_module`` stat of its event where the trace has no runs (the
CPU's).  Its scope path is the ``tf_op`` stat of its event *metadata*,
which ``jax.profiler.ProfileData`` does not expose: :func:`op_scopes`
reads it from the ``.xplane.pb``'s protobuf wire format.  A program
that has no spans or names reads as none.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Tuple

import devtrace

Interval = devtrace.Interval
OpKey = Tuple[int, str, float]                # (device, op name, start s)

_TPU = re.compile(r"^/device:TPU:(\d+)$")
_RUN = re.compile(r"^(.*?)(?:\((\d+)\))?$")   # program(id)


@dataclasses.dataclass
class EngineTrace:
    spans: List[Interval]                      # serve.* spans, by start
    op_info: Dict[OpKey, Tuple[str, str]]      # -> (program, scope path)

    def program_of(self, dev: int, op: Interval) -> str:
        """Name of the program an operation of device ``dev`` ran in
        (``jit_serve_decode``), "" when the trace does not say."""
        return self.op_info.get((dev, op[0], op[1]), ("", ""))[0]

    def scope_of(self, dev: int, op: Interval) -> str:
        """An operation's scope path
        (``jit(serve_decode)/while/body/closed_call/attention/...``),
        "" when the trace does not say."""
        return self.op_info.get((dev, op[0], op[1]), ("", ""))[1]


def of(r) -> EngineTrace:
    """The engine's part of a reading's trace, read once per reading."""
    et = getattr(r, "engine_trace", None)
    if et is None:
        path = devtrace.find_xplane(str(r.ctx.trace_dir))
        et = r.engine_trace = read(path, r.trace)
    return et


def read(path: str, trace: devtrace.Trace) -> EngineTrace:
    """The engine's spans in the file at ``path``, and the program and
    scope of each operation of ``trace`` (``devtrace.load`` of it)."""
    host = devtrace.load(path, lambda plane, line: None, "serve.")
    # TraceMe appends a span's metadata to its name as "#k=v,...#"
    spans = [(n.split("#", 1)[0], s, e) for n, s, e in host.spans]
    info: Dict[OpKey, Tuple[str, str]] = {}
    named = _programs_of_host_ops(path)
    for d, ops in trace.ops.items():
        for name, s, _ in ops:
            prog = named.get((name, s))
            if prog:
                info[d, name, s] = (prog, "")
    programs_by_time(trace.ops, trace.modules, info, op_scopes(path))
    return EngineTrace(spans, info)


def programs_by_time(ops: Dict[int, List[Interval]],
                     runs: Dict[int, List[Interval]],
                     info: Dict[OpKey, Tuple[str, str]],
                     scopes: Dict[Tuple[int, str, str], str]) -> None:
    """Name, in ``info``, each operation's program by the run on its
    device that covers its start (where its stats named none), and its
    scope path by ``scopes[device, program id, op name]``."""
    for d, rs in runs.items():
        i = 0
        for name, s, _ in ops.get(d, []):
            while i < len(rs) and rs[i][2] <= s:
                i += 1
            if i == len(rs):
                break
            if rs[i][1] > s:
                continue
            m = _RUN.match(rs[i][0])
            prog = info.get((d, name, s), ("", ""))[0] or m.group(1)
            scope = scopes.get((d, m.group(2), name.lstrip("%")), "")
            info[d, name, s] = (prog, scope)


def program(run_name: str) -> str:
    """A program run's name without the id the trace appends
    (``jit_serve_decode(123)``)."""
    return _RUN.match(run_name).group(1)


def _programs_of_host_ops(path: str) -> Dict[Tuple[str, float], str]:
    """{(op name, start s): program} of the operations that host planes
    carry with an ``hlo_module`` stat (the CPU client's)."""
    from jax.profiler import ProfileData
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                prog = dict(ev.stats).get("hlo_module")
                if prog:
                    out[ev.name.split(" = ", 1)[0],
                        ev.start_ns * 1e-9] = str(prog)
    return out


def op_scopes(path: str) -> Dict[Tuple[int, str, str], str]:
    """{(TPU device, program id, op name): scope path} from the event
    metadata of each TPU plane of an ``.xplane.pb``.  The fields read,
    of ``tsl/profiler/protobuf/xplane.proto``: ``XSpace.planes`` (1);
    ``XPlane.name`` (2), ``.event_metadata`` (4, a map whose values are
    field 2) and ``.stat_metadata`` (5, likewise); ``XEventMetadata
    .display_name`` (4) and ``.stats`` (5); ``XStatMetadata.id`` (1) and
    ``.name`` (2); ``XStat.metadata_id`` (1) and its value (3 and 4
    integers, 5 a string, 7 a reference to a stat metadata's name).  A
    program id is the number in its runs' names; the ``tf_op`` stat
    ends in ``:`` and the op's type, empty for JAX."""
    with open(path, "rb") as f:
        buf = f.read()
    out: Dict[Tuple[int, str, str], str] = {}
    for field, val in _fields(buf, 0, len(buf)):
        if field != 1:
            continue
        name, events, stat_names = "", [], {}
        for g, v in _fields(buf, *val):
            if g == 2:
                name = buf[v[0]:v[1]].decode()
            elif g in (4, 5):
                entry = dict(_fields(buf, *v))
                if 2 not in entry:
                    continue
                if g == 4:
                    events.append(entry[2])
                else:
                    md = dict(_fields(buf, *entry[2]))
                    if 1 in md and 2 in md:
                        stat_names[md[1]] = buf[md[2][0]:md[2][1]].decode()
        m = _TPU.match(name)
        if not m:
            continue
        for ev in events:
            op, stats = "", {}
            for g, v in _fields(buf, *ev):
                if g == 4:
                    op = buf[v[0]:v[1]].decode()
                elif g == 5:
                    st = dict(_fields(buf, *v))
                    key = stat_names.get(st.get(1))
                    if 5 in st:
                        stats[key] = buf[st[5][0]:st[5][1]].decode()
                    elif 7 in st:
                        stats[key] = stat_names.get(st[7], "")
                    elif 3 in st or 4 in st:
                        stats[key] = str(st.get(3, st.get(4)))
            if op and "program_id" in stats and "tf_op" in stats:
                out[int(m.group(1)), stats["program_id"], op] = \
                    stats["tf_op"].rsplit(":", 1)[0]
    return out


def _fields(buf: bytes, i: int, end: int):
    """(field number, value) of the protobuf message in ``buf[i:end]``:
    an integer for a varint, a (start, end) pair for a length-delimited
    value; fixed-width values are skipped."""
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            val, i = (i, i + n), i + n
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"protobuf wire type {wire} at {i}")
        yield key >> 3, val


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    val = shift = 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        if b < 0x80:
            return val, i
        shift += 7
