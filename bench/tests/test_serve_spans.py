"""The serving cell traced at tiny widths on the CPU, with every sample
made slower: the engine-span readers see the injected idle time where
it was put."""

import time

import jax

import run
import serve_cell
import tiny

CELL = "serve.qwen3-8b.chat"
PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}
SHARES = ("sample_idle_share.serve", "schedule_idle_share.serve",
          "tier_idle_share.serve")


def cpu_lines(plane, line):
    return (0, "ops") if line.startswith("tf_XLAPjRtCpuClient") else None


def test_slow_sampling_shows_under_serve_sample(tmp_path, monkeypatch):
    """With every sample 5 ms slower inside the window, the device idles
    under ``serve.sample`` for at least 80% of that time."""
    from repro.serve.engine import ServeEngine
    orig_sample, orig_window = ServeEngine._sample, serve_cell.window
    slept = []
    in_window = [False]

    def slow(self, r, logits, key):
        if in_window[0]:
            t = time.perf_counter()
            time.sleep(0.005)
            slept.append(time.perf_counter() - t)
        return orig_sample(self, r, logits, key)

    def window(ctx, st):
        in_window[0] = True
        try:
            return orig_window(ctx, st)
        finally:
            in_window[0] = False

    monkeypatch.setattr(ServeEngine, "_sample", slow)
    monkeypatch.setattr(serve_cell, "window", window)
    out = run.run_cell(tiny.spec(CELL), 2**31 + 77, 2.0, True,
                       jax.devices(), tmp_path, compile_cache=False,
                       device_lines=cpu_lines, peaks=PEAKS)
    assert out["correct"], out["checks"]
    m = {k: v["value"] for k, v in out["metrics"].items()}
    injected = 100.0 * sum(slept) / out["device"]["window_s"]
    assert injected > 5
    assert m["sample_idle_share.serve"] >= 0.8 * injected
    assert sum(m[k] for k in SHARES) <= m["idle_share.serve"] + 1e-9
    assert m["prefill_ms.serve"] > 0
    # the CPU trace has no scopes: the decode's attention reads nothing
    assert "decode_attention_ms.serve" not in m
