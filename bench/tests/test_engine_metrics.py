"""The readers of the serving engine's spans and program names, on
hand-made traces: idle time goes to the innermost engine span, program
time to the program's spans and scopes, and a program without spans or
names reads nothing."""

import types

import pytest

import devtrace
import enginetrace
import run

NEW = ("sample_idle_share.serve", "schedule_idle_share.serve",
       "tier_idle_share.serve", "prefill_ms.serve",
       "decode_attention_ms.serve")


def reading(ops, spans, mods=(), scopes=None, window=(0.0, 10.0)):
    """A reading of a hand-made trace: ``spans`` are the engine's,
    ``scopes`` its ops' by (device, program id, op name)."""
    info = {}
    mods = {0: list(mods)}
    ops = {0: sorted(ops, key=lambda x: x[1])}
    enginetrace.programs_by_time(ops, mods, info, scopes or {})
    tr = devtrace.Trace(ops, mods, [("bench.window", *window)])
    et = enginetrace.EngineTrace(sorted(spans, key=lambda x: x[1]), info)
    return types.SimpleNamespace(kind="serve", trace=tr, engine_trace=et,
                                 devices=lambda: [0],
                                 window=lambda: tr.span("bench.window"))


def test_idle_goes_to_the_innermost_engine_span():
    r = reading(ops=[("f.1", 1.0, 2.0), ("f.2", 4.0, 5.0)],
                spans=[("serve.step", 0.0, 6.0), ("serve.admit", 0.5, 1.5),
                       ("serve.spill", 0.6, 0.8), ("serve.sample", 3.0, 6.0)])
    # idle [0, 1], [2, 4], [5, 10]: spill 0.2, admit 0.3, sample 2,
    # the tick itself 1.5, outside any engine span 4
    m = {k: run.read_metric(k, r) for k in NEW[:3]}
    assert m["sample_idle_share.serve"] == pytest.approx(20.0)
    assert m["schedule_idle_share.serve"] == pytest.approx(3.0)
    assert m["tier_idle_share.serve"] == pytest.approx(2.0)
    assert sum(m.values()) <= run.read_metric("idle_share.serve", r) + 1e-9


def test_program_time_under_its_spans_and_scopes():
    decode = "jit(serve_decode)/while/body/closed_call"
    ops = [("%a.1", 0.1, 0.5), ("%g.2", 0.3, 0.6), ("%m.3", 0.7, 1.9),
           ("%a.1", 3.2, 3.4), ("%p.4", 6.1, 6.5), ("%p.5", 6.4, 6.9)]
    scopes = {(0, "3", "a.1"): decode + "/attention/dot_general",
              (0, "3", "g.2"): decode + "/attention/paged_gather/gather",
              (0, "3", "m.3"): decode + "/mlp/dot_general",
              (0, "4", "p.4"): "jit(serve_prefill)/attention/dot"}
    r = reading(ops, spans=[("serve.prefill", 5.9, 7.2)], scopes=scopes,
                mods=[("jit_serve_decode(3)", 0.0, 2.0),
                      ("jit_serve_decode(3)", 3.0, 5.0),
                      ("jit_serve_prefill(4)", 6.0, 7.0)])
    # attention: [0.1, 0.6] in the first run, [3.2, 3.4] in the second
    assert run.read_metric("decode_attention_ms.serve", r) == \
        pytest.approx(350.0)
    assert run.read_metric("prefill_ms.serve", r) == pytest.approx(800.0)


def test_a_program_without_spans_or_names_reads_nothing():
    r = reading(ops=[("fusion.1", 1.0, 2.0), ("copy.2", 3.0, 4.0)],
                spans=[], mods=[("jit__lambda(1)", 0.9, 4.1)])
    for name in NEW:
        assert run.read_metric(name, r) is None, name
    assert run.read_metric("idle_share.serve", r) == pytest.approx(80.0)
