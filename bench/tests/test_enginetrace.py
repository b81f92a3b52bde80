"""Reading the serving engine's part of a profiler trace: its spans,
each operation's program and scope path; on the recorded CPU trace, on
a trace recorded on a TPU and on hand-made intervals."""

from pathlib import Path

import pytest

import devtrace
import enginetrace

DATA = Path(__file__).parent / "data"


def cpu_lines(plane, line):
    return (0, "ops") if line.startswith("tf_XLAPjRtCpuClient") else None


def test_readings_of_the_recorded_cpu_trace_are_pinned():
    """What ``devtrace`` reads of the recorded CPU trace, which engine
    spans must leave as it was."""
    path = str(DATA / "cpu_trace.xplane.pb")
    tr = devtrace.load(path, cpu_lines)
    lo, hi = tr.span("bench.window")
    assert (lo, hi) == pytest.approx((0.000104214, 0.022258148), abs=1e-12)
    assert len(tr.ops[0]) == 62 and len(tr.spans) == 9
    assert tr.modules == {}
    assert devtrace.busy(tr.ops[0], lo, hi) == \
        pytest.approx(0.005020896, abs=1e-12)
    gaps = devtrace.gaps(tr.ops[0], lo, hi)
    assert len(gaps) == 21
    assert sum(e - s for s, e in gaps) == pytest.approx(0.017133038,
                                                        abs=1e-12)
    top = devtrace.attribute(gaps, tr.spans)
    assert [n for n, _ in top] == ["bench.wait"] * 4 + ["bench.step"] * 6
    assert top[0][1] == pytest.approx(0.004291066, abs=1e-12)
    name, secs = devtrace.top(devtrace.by_name(tr.ops[0], lo, hi), 1)[0]
    assert name == "dot_general.1"
    assert secs == pytest.approx(0.003925881, abs=1e-12)
    # a program with no engine spans: none read, and every op that the
    # CPU's stats name ran in the one jitted program
    et = enginetrace.read(path, tr)
    assert et.spans == []
    named = {et.program_of(0, op) for op in tr.ops[0]} - {""}
    assert named == {"jit__lambda"}
    assert all(et.scope_of(0, op) == "" for op in tr.ops[0])


def test_engine_spans_are_read_without_their_metadata(tmp_path):
    import jax
    import jax.numpy as jnp
    from repro.runtime.trace import span

    f = jax.jit(lambda x: x @ x)
    x = jnp.ones((8, 8))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with span("serve.step", tick=7):
        with span("serve.prefill", rids=[3, 4], batch=2, tokens=5,
                  aliased=0):
            f(x).block_until_ready()
        with span("other.thing"):
            pass
    jax.profiler.stop_trace()
    path = devtrace.find_xplane(str(tmp_path))
    et = enginetrace.read(path, devtrace.load(path, cpu_lines))
    assert [n for n, _, _ in et.spans] == ["serve.step", "serve.prefill"]
    (_, a, b), (_, c, d) = et.spans
    assert a <= c < d <= b


def test_programs_by_time_fill_what_stats_do_not_say():
    ops = {0: [("%fusion.1", 1.0, 2.0), ("%copy.2", 2.5, 3.0),
               ("%fusion.1", 5.0, 6.0), ("%idle.op", 7.5, 8.0)]}
    runs = {0: [("jit_serve_decode(12)", 0.5, 3.5),
                ("jit_serve_prefill(13)", 4.5, 7.0)]}
    info = {(0, "%copy.2", 2.5): ("jit_named_by_stat", "")}
    scopes = {(0, "12", "fusion.1"): "jit(serve_decode)/attention/dot",
              (0, "13", "copy.2"): "jit(serve_prefill)/mlp/copy"}
    enginetrace.programs_by_time(ops, runs, info, scopes)
    et = enginetrace.EngineTrace([], info)
    assert [et.program_of(0, op) for op in ops[0]] == \
        ["jit_serve_decode", "jit_named_by_stat", "jit_serve_prefill", ""]
    assert [et.scope_of(0, op) for op in ops[0]] == \
        ["jit(serve_decode)/attention/dot", "", "", ""]
    assert enginetrace.program("jit_serve_decode(12)") == "jit_serve_decode"
    assert enginetrace.program("jit__lambda") == "jit__lambda"


def test_scopes_of_a_recorded_tpu_trace():
    """A jitted scan with ``attention/paged_gather``, ``mlp`` and
    ``logits`` scopes, traced on one TPU v5e inside ``serve.step`` and
    ``serve.prefill`` spans (source paths and HLO protos taken out of
    the file): each operation's scope path comes from its event
    metadata, its program from the run that covers it."""
    path = str(DATA / "tpu_trace.xplane.pb")
    tr = devtrace.load(path)
    et = enginetrace.read(path, tr)
    assert [n for n, _, _ in et.spans] == ["serve.step", "serve.prefill"]
    (run, a, b), = tr.modules[0]
    assert enginetrace.program(run) == "jit_serve_f"
    ops = tr.ops[0]
    assert len(ops) == 24 and all(a <= s < b for _, s, _ in ops)
    assert {et.program_of(0, op) for op in ops} == {"jit_serve_f"}
    scope = {op[0]: et.scope_of(0, op) for op in ops}
    body = "jit(serve_f)/while/body/closed_call/"
    assert scope["%fusion.22"] == body + "attention/paged_gather/gather"
    assert scope["%convolution_add_fusion.2"] == body + "mlp/dot_general"
    assert scope["%fusion.10"] == "jit(serve_f)/logits/dot_general"
    # the copy of the undonated pool out of the program has no scope
    assert scope["%copy.13"] == "" and scope["%while"] == ""
    assert len(enginetrace.op_scopes(path)) == 7
