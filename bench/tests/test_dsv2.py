"""The DeepSeek-V2-Lite cell at tiny widths on the CPU: the float32
reference against prefill then paged latent decode through
``ServeEngine`` (one request admitted on an aliased document), the
cell driven end to end and traced, the control read, and the MLA
operation and byte counts against a hand count."""

import copy
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import counts_mla
import run

CELL = "serve.dsv2-lite.docqa"
PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}
DSV2 = json.load(open(run.BENCH / "configs" / "deepseek-v2-lite.e8.json"))
TINY_MODEL = {"n_layers": 3, "d_model": 64, "n_heads": 4, "d_ff": 96,
              "vocab_size": 256, "n_experts": 8, "experts_per_token": 3,
              "moe_d_ff": 32, "n_routed_experts": 4, "kv_lora_rank": 16,
              "qk_nope_head_dim": 8, "qk_rope_head_dim": 8,
              "v_head_dim": 8}
TINY_MIX = {"rate_rps": 15.0, "system_len": 32, "system_prompts": 3,
            "user_lens": [5, 12, 40], "user_median": 12, "out_median": 6,
            "out_min": 3, "out_max": 12, "warm_group": 2,
            "check_requests": 3}
# at these widths the program's widest gap is about 0.002 and the fp8
# control's about 0.01 (CPU readings): the cell's limit, set at the
# published widths, would pass the control here
TINY_LIMITS = {"served_gap_max": 0.005}


def tiny_spec():
    s = copy.deepcopy(run.load_spec(CELL))
    s["config"]["model"].update(TINY_MODEL)
    s["config"]["serve"] = {"max_batch": 4, "max_len": 128}
    s["mix"].update(TINY_MIX)
    s["own"]["limits"] = dict(TINY_LIMITS)
    return s


class _Meter:
    s = 0.0


def cpu_lines(plane, line):
    return (0, "ops") if line.startswith("tf_XLAPjRtCpuClient") else None


def _run(tmp_path, **kw):
    return run.run_cell(tiny_spec(), 2**31 + 91, 2.0, kw.pop("trace", False),
                        jax.devices(), tmp_path, compile_cache=False, **kw)


def test_engine_logits_match_deepseek_v2_reference(tmp_path):
    import serve_cell
    from reference import deepseek_v2
    from repro.serve.engine import ServeEngine

    rows = {}
    orig = ServeEngine._sample

    def probe(self, r, logits, key):
        rows.setdefault(r.rid, []).append(
            np.asarray(logits[:self.arch.vocab_size].astype(jnp.float32)))
        return orig(self, r, logits, key)

    ctx = run.Ctx(tiny_spec(), 5, 1.0, False, jax.devices(), _Meter(),
                  tmp_path)
    st = serve_cell.setup(ctx)
    eng = st["engine"]
    assert eng.cfg.moe_impl == "held_dropless" and eng._pools == ("ckv",)
    eng._sample = probe.__get__(eng)
    rng = np.random.default_rng(0)
    V = ctx.arch.vocab_size
    doc = rng.integers(0, V, 32, dtype=np.int32)
    prompts = [np.concatenate([doc, rng.integers(0, V, 9, dtype=np.int32)]),
               np.concatenate([doc, rng.integers(0, V, 20, dtype=np.int32)]),
               rng.integers(0, V, 13, dtype=np.int32)]
    stats0 = eng.pressure_stats()
    rids = [eng.submit(prompts[0], max_new_tokens=6)]
    eng.step()                          # the document is now resident
    rids += [eng.submit(p, max_new_tokens=6) for p in prompts[1:]]
    while eng.pending or eng.active:
        eng.step()
    stats = eng.pressure_stats()
    assert stats["prefix_hit_tokens"] - stats0["prefix_hit_tokens"] >= 32
    assert stats["moe_held_pairs"]["decode"] > \
        stats0["moe_held_pairs"]["decode"]
    done = {r.rid: r for r in eng.finished}
    seqs, at = [], []
    for rid, p in zip(rids, prompts):
        out = done[rid].out_tokens
        seqs.append(np.concatenate([p, np.asarray(out[:-1], np.int32)]))
        at.append(np.arange(len(p) - 1, len(p) - 1 + len(out)))
    ref = deepseek_v2.served_logits(ctx.config["model"], ctx.seed, seqs, at)
    for rid, r in zip(rids, ref):
        got = np.stack(rows[rid])
        scale = np.abs(r).max()
        # bf16 weights are shared; bf16 activations, latent rows and
        # logits are not, nor the absorbed order of the decode's products
        assert np.abs(got - r).max() <= 0.02 * scale, rid


def test_sound_run_is_correct_and_the_control_is_not(tmp_path):
    out = _run(tmp_path, control=True)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 20 and out["failed"] == 0
    assert set(out["metrics"]) == {"ttft_p50_ms", "itl_p95_ms",
                                   "serve_tokens_per_s", "setup_s"}
    assert not out["control"]["correct"]
    c = out["control"]["checks"]["served_gap_max"]
    assert c["value"] > c["limit"] > out["checks"]["served_gap_max"]["value"]


def test_traced_run_reports_the_new_metrics(tmp_path):
    out = _run(tmp_path, trace=True, device_lines=cpu_lines, peaks=PEAKS)
    m = out["metrics"]
    for k in ("prefix_hit_token_share", "idle_share.serve",
              "mfu.serve.dsv2"):
        assert k in m, k
    # the CPU trace has no program runs: the decode readers read none
    assert "mfu.serve" not in m and "decode_hbm_roofline" not in m
    assert 0 < m["mfu.serve.dsv2"]["value"] < 100


# ---------------------------------------------------------------------
# counts_mla against a hand count at the published widths
# ---------------------------------------------------------------------

M = DSV2["model"]


def test_hand_count_of_one_prefill_and_one_decode():
    d, V = 2048, 102400
    attn = 2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256 + 16 * 128 * 2048
    assert counts_mla.attn_params(M) == attn == 13_762_560
    dense = attn + 3 * 2048 * 10944
    moe = attn + 2048 * 64 + 3 * 2048 * 2816      # router, 2 shared
    expert = 3 * 2048 * 1408
    # a prefill of positions 0 and 1 (1 + 2 keys seen), 7 held pairs
    tok = 2 * (dense + 26 * moe)
    want = 2 * tok + 7 * 2 * expert \
        + 27 * 2 * 16 * (192 + 128) * 3 + 2 * d * V
    assert counts_mla.prefill_flops(M, 0, 2, 7) == want
    # one decode of slots with 100 and 300 cached positions, 5 pairs:
    # every head attends over the 576-wide latent, context 512 wide
    want = 2 * tok + 5 * 2 * expert \
        + 27 * 2 * 16 * (512 + 576) * 400 + 2 * 2 * d * V
    assert counts_mla.decode_flops(M, [100, 300], 5) == want
    held = dense + 26 * (moe - 2048 * 64) + 26 * 8 * expert
    row = 27 * 576 * 2
    assert counts_mla.latent_bytes_per_token(M) == row == 31104
    assert counts_mla.decode_bytes(M, [100, 300]) == \
        2 * held + 26 * 2048 * 64 * 4 + 2 * d * V + 2 * d * 2 + row * 400


def test_param_bytes_match_the_program_leaves():
    """The held weights the counts read equal the program's own
    matrices (norm scales aside) at the published widths."""
    from repro.configs.base import ArchConfig
    from repro.models import lm
    import dataclasses
    fields = {f.name for f in dataclasses.fields(ArchConfig)}
    arch = ArchConfig(**{k: v for k, v in M.items() if k in fields})
    shapes = jax.eval_shape(lambda k: lm.init_params(arch, k),
                            jax.random.PRNGKey(0))
    mats = sum(x.size * x.dtype.itemsize
               for p, x in jax.tree_util.tree_leaves_with_path(shapes)
               if x.ndim >= 2 + ("blocks" in str(p[0]) or "lead" in str(p[0]))
               and "embed" not in str(p[0]) and "lm_head" not in str(p[0]))
    assert counts_mla.param_bytes(M)["layers"] == mats


def test_moe_time_under_its_scope_in_decode_runs():
    from test_engine_metrics import reading
    decode = "jit(serve_decode)/while/body/closed_call/mlp/moe"
    ops = [("%r.1", 0.1, 0.2), ("%h.2", 0.15, 0.5), ("%a.3", 0.6, 0.9),
           ("%h.2", 3.1, 3.3), ("%p.4", 6.1, 6.5)]
    scopes = {(0, "3", "r.1"): decode + "/router/dot_general",
              (0, "3", "h.2"): decode + "/held/ragged_dot",
              (0, "3", "a.3"): "jit(serve_decode)/attention/dot",
              (0, "4", "p.4"): "jit(serve_prefill)/moe/held/ragged_dot"}
    r = reading(ops, spans=[], scopes=scopes,
                mods=[("jit_serve_decode(3)", 0.0, 2.0),
                      ("jit_serve_decode(3)", 3.0, 5.0),
                      ("jit_serve_prefill(4)", 6.0, 7.0)])
    # moe: [0.1, 0.5] in the first run, [3.1, 3.3] in the second; the
    # prefill's is not a decode run's
    assert run.read_metric("moe_ms.serve", r) == pytest.approx(300.0)
    r = reading(ops[2:3], spans=[], scopes=scopes,
                mods=[("jit_serve_decode(3)", 0.0, 2.0)])
    assert run.read_metric("moe_ms.serve", r) is None


def test_pair_readers_read_nothing_without_marks():
    from test_engine_metrics import reading
    r = reading(ops=[("f.1", 1.0, 2.0)], spans=[])
    r.moe_marks = []
    for name in ("mfu.serve.dsv2", "decode_hbm_roofline.dsv2"):
        assert run.read_metric(name, r) is None, name


def test_decode_roofline_over_decode_only_ticks():
    import types
    import devtrace
    tr = devtrace.Trace({0: [("f.1", 1.0, 1.5), ("f.2", 2.0, 2.5)]},
                        {0: [("jit_serve_decode(3)", 1.0, 1.5),
                             ("jit_serve_decode(3)", 2.0, 2.5)]},
                        [("bench.window", 0.0, 10.0)])
    ticks = [{"start": 0.9, "end": 1.6, "prefill": [], "decode_ctx": [100]},
             {"start": 1.9, "end": 2.6, "prefill": [], "decode_ctx": [300]}]
    r = types.SimpleNamespace(
        kind="serve", trace=tr, res={"ticks": ticks}, model=M, peaks=PEAKS,
        devices=lambda: [0], window=lambda: tr.span("bench.window"),
        moe_marks=[(1.55, "decode", 4), (2.55, "decode", 2)])
    least = sum(max(counts_mla.decode_bytes(M, [c]) / 1e11,
                    counts_mla.decode_flops(M, [c], n) / 1e12)
                for c, n in ((100, 4), (300, 2)))
    assert run.read_metric("decode_hbm_roofline.dsv2", r) == \
        pytest.approx(100.0 * least / 1.0)
