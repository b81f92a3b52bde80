"""A serving cell: open-loop requests into ``ServeEngine.step``.

Set-up specializes a decode plan for the cell's shape, makes the
weights from the seed, builds the engine with ``ServeEngine.from_plan``
and warms every program the mix can call: each prompt length, with and
without an aliased system prompt, in admission groups of 1 to
``warm_group`` requests, and the host-tier spill and promote paths.

The window sends each request when it is due and ticks the engine;
the time of every token is the end of the tick that produced it.  It
closes after ``seconds``, once every request due inside it has its
first token (at most a minute later).  Then a sample of finished
requests, drawn from the seed with the longest among them, is checked
against the float32 reference: the widest gap by which a served token's
logit lies below the reference's best at that position.
"""

from __future__ import annotations

import dataclasses
import gc
import time
from typing import Any, Dict, List

import numpy as np

import gen


@dataclasses.dataclass
class Tracked:
    req: gen.Req
    obj: Any = None                  # the engine's Request
    sent: float = -1.0               # window clock when submitted
    admitted_tick_start: float = -1.0
    refused: str = ""
    times: List[float] = dataclasses.field(default_factory=list)


def _submit(engine, t: Tracked) -> bool:
    """Send one request; False when the engine refuses it."""
    try:
        rid = engine.submit(t.req.prompt, max_new_tokens=t.req.max_new)
    except (RuntimeError, ValueError) as e:       # OverloadError is one
        t.refused = str(e)
        return False
    t.obj = next(r for r in reversed(engine.pending) if r.rid == rid)
    return True


def _run_until_idle(engine, limit: int = 10000) -> None:
    n = 0
    while engine.pending or engine.active or engine.preempted:
        engine.step()
        n += 1
        if n > limit:
            raise RuntimeError("warm-up did not drain")


def warm(engine, mix: Dict, vocab: int, seed: int, log) -> int:
    """Drive every admission shape the mix can produce through the
    engine once; returns the number of warm requests."""
    rng = np.random.default_rng([seed, 7])
    shapes = gen.warm_shapes(mix)
    sys_len = shapes["system"][0] if shapes["system"] else 0
    n_max = int(mix.get("warm_group", 4))
    count = 0

    def group(lens, prefix=None):
        nonlocal count
        for L in lens:
            p = rng.integers(0, vocab, L, dtype=np.int32)
            if prefix is not None:
                p = np.concatenate([prefix, p])
            engine.submit(p, max_new_tokens=2)
            count += 1

    for n in range(1, n_max + 1):
        for u in shapes["user"]:
            group([u] * n)                              # no system prompt
            engine.step()
            if sys_len:
                sp = rng.integers(0, vocab, sys_len, dtype=np.int32)
                group([u] * n, sp)                      # system prompt, miss
                engine.step()
                group([u] * n, sp)                      # system prompt, hit
                engine.step()
            _run_until_idle(engine)
    if sys_len and getattr(engine, "kv_tiering", False):
        # a hit on spilled blocks promotes them back: both blocks of a
        # system prompt, and one
        for n_spill in (None, 1):
            sp = rng.integers(0, vocab, sys_len, dtype=np.int32)
            group([shapes["user"][0]], sp)
            _run_until_idle(engine)
            if n_spill is None:
                engine.spill_cached()
            else:
                engine.drop_block_cache()
                group([shapes["user"][0]], sp)
                _run_until_idle(engine)
                engine._spill_cold(0, n_spill)
            group([shapes["user"][0]], sp)
            _run_until_idle(engine)
    if hasattr(engine, "drop_block_cache"):
        engine.drop_block_cache()
    return count


def setup(ctx) -> Dict[str, Any]:
    import jax
    from repro.configs import ShapeConfig
    from repro.core.pipeline import specialize
    from repro.launch.env import plan_target
    from repro.launch.mesh import make_mesh
    from repro.models import lm
    from repro.serve.engine import ServeEngine

    import weights
    arch, cfg, mix = ctx.arch, ctx.config, ctx.mix
    shape = ShapeConfig("serve", "decode", cfg["serve"]["max_len"],
                        cfg["serve"]["max_batch"])
    mesh = make_mesh((1, ctx.chips), ("data", "model"),
                     devices=ctx.devices[:ctx.chips])
    t = time.perf_counter()
    plan = specialize(arch, shape, mesh_axes=tuple(mesh.axis_names),
                      mesh_shape=tuple(mesh.devices.shape),
                      target=plan_target(), cache=False)
    ctx.setup["specialize_s"] = time.perf_counter() - t
    shapes = jax.eval_shape(
        lambda k: lm.init_params(arch, k, *plan.padded_sizes()),
        jax.random.PRNGKey(0))
    params = weights.make(shapes, ctx.seed, arch.n_layers)
    engine = ServeEngine.from_plan(plan, params, arch=arch, mesh=mesh,
                                   seed=ctx.seed)
    del params
    ctx.line(f"plan {plan.content_hash()[:12]} decode_path "
             f"{engine.decode_path} kv_residency {engine.kv_residency} "
             f"kv_admission {engine.kv_admission} pool_blocks "
             f"{engine.n_blocks}x{engine.block_len} host_blocks "
             f"{engine.host_blocks} prefill_mode {engine.prefill_mode} "
             f"max_batch {engine.max_batch} max_len {engine.max_len}")
    n = warm(engine, mix, arch.vocab_size, ctx.seed, ctx.line)
    ctx.line(f"warm-up: {n} requests, {engine.prefill_calls} prefill calls")
    return {"engine": engine, "plan": plan}


def window(ctx, st) -> Dict[str, Any]:
    engine = st["engine"]
    W = ctx.seconds
    reqs = gen.serve_requests(ctx.mix, ctx.seed, ctx.arch.vocab_size, W,
                              W + 60.0, engine.max_len)
    tracked = [Tracked(r) for r in reqs]
    live: List[Tracked] = []
    ticks: List[Dict[str, Any]] = []
    # prefix hits and prompt tokens, both over the requests admitted in
    # ticks that started inside the window
    hit_tokens = admitted_prompt_tokens = 0
    at_close = None
    nxt = 0
    t0 = ctx.begin_window()
    traced = ctx.trace
    while True:
        now = time.perf_counter() - t0
        if nxt < len(tracked) and tracked[nxt].req.due <= now:
            with ctx.span("bench.submit"):
                while nxt < len(tracked) and tracked[nxt].req.due <= now:
                    t = tracked[nxt]
                    t.sent = now
                    if _submit(engine, t):
                        live.append(t)
                    nxt += 1
        if engine.pending or engine.active or engine.preempted:
            ts = time.perf_counter() - t0
            hits0 = engine.pressure_stats()["prefix_hit_tokens"]
            with ctx.span("bench.step"):
                engine.step()
            te = time.perf_counter() - t0
            hits = engine.pressure_stats()["prefix_hit_tokens"] - hits0
            tick = {"start": ts, "end": te, "prefill": [], "decode_ctx": [],
                    "hit_tokens": hits}
            done = []
            for t in live:
                r = t.obj
                n0 = len(t.times)
                if t.admitted_tick_start < 0 and (r.slot >= 0 or r.out_tokens):
                    t.admitted_tick_start = ts
                    tick["prefill"].append((len(r.prompt),
                                            t.req.system >= 0))
                    if ts < W:
                        admitted_prompt_tokens += len(r.prompt)
                new = len(r.out_tokens) - n0
                if new > 0:
                    t.times.extend([te] * new)
                    # tokens after the first come from the decode call,
                    # which read the cache up to and including its input
                    tick["decode_ctx"].extend(
                        len(r.prompt) + j for j in range(max(n0, 1), n0 + new))
                if r.done or r.error:
                    done.append(t)
            if ts < W:
                hit_tokens += hits
            if traced:
                ticks.append(tick)
            for t in done:
                live.remove(t)
        else:
            with ctx.span("bench.wait"):
                if nxt < len(tracked):
                    time.sleep(max(0.0, min(
                        tracked[nxt].req.due - (time.perf_counter() - t0),
                        0.005)))
        now = time.perf_counter() - t0
        if now >= W:
            if at_close is None:
                at_close = (len(engine.active), len(engine.pending))
            if now >= W + 60.0 or all(
                    t.times or t.refused or t.obj.error
                    for t in tracked[:nxt]
                    if t.req.due < W):
                break
    close = time.perf_counter() - t0
    ctx.end_window()
    due = [t for t in tracked[:nxt] if t.req.due < W]
    late = [t.sent - t.req.due for t in tracked[:nxt]]
    failed = [t for t in due if not t.times]
    ttft = [t.times[0] - t.req.due for t in due if t.times]
    itl = [b - a for t in tracked[:nxt] for a, b in zip(t.times, t.times[1:])
           if b < W]
    out_tokens = sum(1 for t in tracked[:nxt] for x in t.times if x < W)
    qwait = [t.admitted_tick_start - t.req.due for t in due
             if t.admitted_tick_start >= 0]

    def pct(xs, q):
        return f"{np.percentile(xs, q) * 1e3:.1f}" if len(xs) else "-"

    thirds = [[t.admitted_tick_start - t.req.due for t in due
               if t.admitted_tick_start >= 0
               and k * W / 3 <= t.req.due < (k + 1) * W / 3]
              for k in range(3)]
    ctx.line(f"window: {len(due)} requests due, {len(failed)} without a "
             f"first token, {out_tokens} tokens, closed at {close:.3f}s; "
             f"generator lateness p50 {np.percentile(late, 50)*1e3:.3f} ms "
             f"max {max(late)*1e3:.3f} ms; active {at_close[0]} and "
             f"pending {at_close[1]} at {W}s")
    ctx.line(f"window: ttft p50/p90/p95 ms {pct(ttft, 50)} {pct(ttft, 90)} "
             f"{pct(ttft, 95)}; itl p50/p95 ms {pct(itl, 50)} {pct(itl, 95)}; "
             f"queue wait p50 ms by thirds of the window "
             f"{' '.join(pct(x, 50) for x in thirds)}; prefix hit tokens "
             f"{hit_tokens} of {admitted_prompt_tokens} admitted")
    return {"attempted": len(due), "n_failed": len(failed),
            "due": due, "tracked": tracked[:nxt], "failed": failed,
            "ttft": ttft, "itl": itl, "out_tokens": out_tokens,
            "qwait": qwait, "ticks": ticks, "hit_tokens": hit_tokens,
            "admitted_prompt_tokens": admitted_prompt_tokens,
            "lateness": late}


def end_to_end(ctx, res) -> Dict[str, float]:
    W = ctx.seconds
    return {"ttft_p50_ms": float(np.percentile(res["ttft"], 50)) * 1e3,
            "itl_p95_ms": float(np.percentile(res["itl"], 95)) * 1e3,
            "serve_tokens_per_s": res["out_tokens"] / W}


def check(ctx, st, res, control: bool = False) -> Dict[str, Any]:
    """Free the engine, then compare a sample of finished requests with
    the reference.  Returns the numbers compared; with ``control`` also
    the control's, under the same names: at each served position the
    token that the reference computed in fp8 puts first, in the served
    token's place."""
    import importlib
    done = [t for t in res["tracked"]
            if t.obj is not None and t.obj.done and not t.obj.error]
    rng = np.random.default_rng([ctx.seed, 11])
    k = int(ctx.mix.get("check_requests", 6))
    longest = max(done, key=lambda t: len(t.obj.out_tokens))
    rest = [t for t in done if t is not longest]
    pick = [longest] + [rest[i] for i in rng.choice(
        len(rest), min(k - 1, len(rest)), replace=False)]
    seqs, at, served = [], [], []
    for t in pick:
        out = np.asarray(t.obj.out_tokens, np.int32)
        p = t.req.prompt
        seqs.append(np.concatenate([p, out[:-1]]))
        at.append(np.arange(len(p) - 1, len(p) - 1 + len(out)))
        served.append(out)
    answered = res["n_failed"] == 0
    st.clear()
    res["tracked"] = res["due"] = None
    gc.collect()
    ref = importlib.import_module("reference." + ctx.config["reference"])
    model = ctx.config["model"]
    rows = ref.served_logits(model, ctx.seed, seqs, at)

    def numbers(tokens, who):
        gaps = [float((r.max(axis=-1) - r[np.arange(len(s)), s]).max())
                for r, s in zip(rows, tokens)]
        ctx.line(f"check ({who}): {len(tokens)} requests, "
                 f"{sum(len(s) for s in tokens)} tokens, gap per request "
                 f"{[round(g, 5) for g in gaps]}")
        return {"served_gap_max": max(gaps),
                "served_tokens_checked": sum(len(s) for s in tokens),
                "tokens_in_vocab": all(
                    ((s >= 0) & (s < ctx.arch.vocab_size)).all()
                    for s in tokens),
                "every_request_answered": answered}

    out = {"program": numbers(served, "program")}
    if control:
        low = ref.served_logits(model, ctx.seed, seqs, at, prec="fp8")
        out["control"] = numbers([c.argmax(-1) for c in low], "control")
    return out
