"""The serving cell driven end to end at tiny widths on the CPU, with a
sound engine, with a served token altered where it is produced, traced,
and with the control read."""

import jax

import run
import tiny

CELL = "serve.qwen3-8b.chat"
PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}


def cpu_lines(plane, line):
    return (0, "ops") if line.startswith("tf_XLAPjRtCpuClient") else None


def _run(tmp_path, **kw):
    return run.run_cell(tiny.spec(CELL), 2**31 + 77, 2.0, kw.pop("trace", False),
                        jax.devices(), tmp_path, compile_cache=False, **kw)


def test_sound_run_is_correct(tmp_path):
    out = _run(tmp_path)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 20 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    m = out["metrics"]
    assert set(m) == {"ttft_p50_ms", "itl_p95_ms", "serve_tokens_per_s",
                      "setup_s"}
    assert all(v["value"] > 0 for v in m.values())


def test_altered_token_is_caught(tmp_path, monkeypatch):
    from repro.serve.engine import ServeEngine
    orig = ServeEngine._sample

    def altered(self, r, logits, key):
        return (orig(self, r, logits, key) + 1) % self.arch.vocab_size

    monkeypatch.setattr(ServeEngine, "_sample", altered)
    out = _run(tmp_path)
    assert not out["correct"]
    c = out["checks"]["served_gap_max"]
    assert c["value"] > c["limit"]


def test_traced_run_reports_per_layer_metrics(tmp_path):
    out = _run(tmp_path, trace=True, device_lines=cpu_lines, peaks=PEAKS)
    m = out["metrics"]
    for k in ("specialize_s", "compile_s", "queue_wait_p50_ms",
              "prefix_hit_token_share", "idle_share.serve", "mfu.serve"):
        assert k in m, k
    assert 0 <= m["prefix_hit_token_share"]["value"] < 100
    assert 0 <= m["idle_share.serve"]["value"] < 100
    assert 0 < out["device"]["busy_s"] <= out["device"]["window_s"]
    assert "ttft_p50_ms" not in m
    assert out["breakdown"]["device_ops"] and out["breakdown"]["idle_gaps"]


def test_control_is_not_correct(tmp_path):
    out = _run(tmp_path, control=True)
    assert out["correct"], out["checks"]
    assert not out["control"]["correct"]
    c = out["control"]["checks"]["served_gap_max"]
    assert c["value"] > c["limit"] > out["checks"]["served_gap_max"]["value"]
    assert list(out)[-1] == "checks"
