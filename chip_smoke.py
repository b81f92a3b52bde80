#!/usr/bin/env python3
"""Smoke run of the main paths on a TPU: does the system still start?

    python chip_smoke.py [--seed 0]             # one chip: serve + train
    python chip_smoke.py --chips 4 [--seed 0]   # the cross-chip paths only

One process drives the chip(s) through the functions the launchers call
(``repro.launch.serve.serve``, ``repro.launch.train.train``), at the
published widths with seeded random weights.

One chip:

* serve — qwen3-8b cut in depth to 16 of 36 layers, then
  deepseek-v2-lite whole with 8 of each MoE layer's 64 routed experts
  held (one chip's share under expert parallelism 8; latent attention
  over a latent paged cache): specialize a decode shape,
  ``ServeEngine.from_plan`` on a one-device mesh, 8 requests of mixed
  prompt lengths, ``run_until_idle``.  Checks: every request
  finished with the requested tokens, every id is in the vocabulary,
  every sampled logits row is finite, and each prompt length's first
  logits agree with a dense one-request ``lm.prefill`` of that prompt.
  The probe samples row by row; the same prompts through a plain engine,
  which samples every row of a tick in one program, give its tokens.
* train — hymba-1.5b cut in depth to 12 of 32 layers (global layers 0
  and 11, windowed 1-10): ``Trainer.fit`` for 3 steps at seq 4096.
  Checks: finite losses, step-0 loss near ln(vocab).

``--chips 4`` runs only what exists across chips, each with what it is
compared with:

* serve — hymba-1.5b whole on a 2x2 (data, model) mesh, where the plan
  picks ``shard_map_flash`` over a 2-D paged pool, against the same
  plan, weights and requests on one device of this process.  Tokens
  must match except at a bf16 near-tie; logits agree within the
  combine tolerance.
* train — the 12-layer hymba on a 4x1 data mesh with the lowered int8
  gradient wire against the fp32 wire, 4 steps each: step 0 identical,
  the loss gap within the trajectory test's bound.

Times printed are smoke times with compiles included, not benchmark
numbers.  The last line of stdout is one JSON object naming the device.
The script exits non-zero, printing no such line, when JAX finds no TPU,
when the repository's ``src/`` is not beside it, or when a check fails.
"""

from __future__ import annotations

import argparse
import gc
import json
import math
import os
import shutil
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent

SERVE_ARCH, SERVE_LAYERS = "qwen3-8b", 16
# 16 layers: weights 8.07 GiB + the plan's 64-block pool 2.00 GiB; the
# decode step's compiled footprint (memory_analysis, v5e) is 12.3 GiB
# deepseek-v2-lite, 27 layers, 8 of 64 experts held: weights 6.2 GB
MLA_ARCH, MLA_HELD = "deepseek-v2-lite", 8
SERVE_BATCH, SERVE_LEN, NEW_TOKENS = 8, 4096, 16
PROMPT_LENS = (96, 480, 1200)      # three prefill buckets
TRAIN_ARCH, TRAIN_LAYERS, TRAIN_SEQ = "hymba-1.5b", 12, 4096
# 12 layers: params + fp32 master + AdamW moments 8.91 GiB, step temps
# 3.11 GiB at batch 1 (memory_analysis, v5e)
# |logit diff| between two paths that round bf16 in different orders
# (bucket padding, batch mix, shard combine); a wrong block or mask is an
# O(1) error (tests/test_multidevice.py uses the same bound)
PATH_TOL = 0.3
NEAR_TIE = 0.15      # runner-up gap that excuses a flipped argmax
WIRE_GAP = 1e-3      # int8 vs fp32 loss (tests/test_train_equivalence.py)


def log(msg: str) -> None:
    print(msg, flush=True)


class CheckFailed(Exception):
    """An output of the run is wrong."""


def check(ok: bool, what) -> None:
    """Raise unless ``ok`` (an ``assert`` that ``python -O`` keeps)."""
    if not ok:
        raise CheckFailed(what)


class CompileMeter:
    """XLA backend compiles (seconds, count) seen since ``reset``."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self, jax):
        self.s, self.n = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == self.EVENT:
            self.s += secs
            self.n += 1

    def reset(self):
        self.s, self.n = 0.0, 0

    def __str__(self):
        return f"xla_compile_s={self.s:.1f} ({self.n} programs)"


def peaks(jax, devices) -> str:
    return " ".join(
        f"dev{d.id}={d.memory_stats()['peak_bytes_in_use'] / 2**30:.2f}GiB"
        for d in devices)


def probe_engine(ServeEngine, np, jnp):
    class Probe(ServeEngine):
        """Records every sampled logits row (fp32, vocab slice) per rid."""

        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self.rows = {}

        def _sample(self, r, logits, key):
            row = logits[:self.arch.vocab_size].astype(jnp.float32)
            self.rows.setdefault(r.rid, []).append(np.asarray(row))
            return super()._sample(r, logits, key)
    return Probe


def check_served(out, n_req, vocab, np):
    done = out.done
    check(len(done) == n_req, f"{len(done)} of {n_req} requests finished")
    for r in done:
        check(len(r.out_tokens) == NEW_TOKENS, (r.rid, r.out_tokens))
        check(all(0 <= t < vocab for t in r.out_tokens),
              (r.rid, r.out_tokens))
        rows = out.engine.rows[r.rid]
        check(len(rows) == NEW_TOKENS, (r.rid, len(rows)))
        check(all(np.isfinite(x).all() for x in rows), f"rid {r.rid}")


def serve_archs():
    """The one-chip serving configurations and what each changes from
    the published model."""
    import dataclasses
    from repro.configs import get_arch, run_arch
    yield run_arch(SERVE_ARCH, layers=SERVE_LAYERS)
    src = get_arch(MLA_ARCH)
    yield (dataclasses.replace(src, n_routed_experts=MLA_HELD),
           {"n_routed_experts": (src.n_experts, MLA_HELD)})


def phase_serve(args, jax, meter, dev, arch, changed):
    import jax.numpy as jnp
    import numpy as np
    from repro.launch.mesh import make_mesh
    from repro.launch.serve import make_prompts, serve
    from repro.models import lm
    from repro.serve.engine import ServeEngine

    log(f"[serve] arch {arch.name} changed from published: {changed}")
    rng = np.random.default_rng(args.seed)
    lens = rng.choice(PROMPT_LENS, SERVE_BATCH)
    prompts = make_prompts(arch.vocab_size, lens, args.seed)
    meter.reset()
    t0 = time.perf_counter()
    mesh = make_mesh((1, 1), ("data", "model"), devices=[dev])
    out = serve(arch, prompts, new_tokens=NEW_TOKENS, max_batch=SERVE_BATCH,
                max_len=SERVE_LEN, seed=args.seed, mesh=mesh,
                engine_cls=probe_engine(ServeEngine, np, jnp))
    wall = time.perf_counter() - t0
    log(f"[serve] {' '.join(f'{k}={v}' for k, v in out.summary().items())}"
        f" prompt_lens={sorted(int(x) for x in lens)}")
    check_served(out, SERVE_BATCH, arch.vocab_size, np)
    # reference: each prompt length's first request, prefilled alone
    # through the dense path, must give the logits its first token
    # was sampled from
    prefill = jax.jit(lambda p, t: lm.prefill(
        arch, p, {"tokens": t}, out.engine.cfg, max_len=t.shape[1])[0])
    worst = rel = 0.0
    seen = set()
    for r in sorted(out.done, key=lambda r: r.rid):
        if len(r.prompt) in seen:
            continue
        seen.add(len(r.prompt))
        ref = np.asarray(prefill(out.engine.params,
                                 jnp.asarray(r.prompt[None]))[0]
                         [:arch.vocab_size], np.float32)
        got = out.engine.rows[r.rid][0]
        diff = float(np.abs(ref - got).max())
        worst = max(worst, diff)
        rel = max(rel, float(np.linalg.norm(ref - got) / np.linalg.norm(ref)))
        check(diff <= PATH_TOL,
              f"rid {r.rid} len {len(r.prompt)}: engine logits off the "
              f"dense prefill by {diff}")
        top = int(ref.argmax())
        check(r.out_tokens[0] == top
              or ref[top] - ref[r.out_tokens[0]] <= NEAR_TIE, (r.rid, top))
    toks = sum(len(r.out_tokens) for r in out.done)
    log(f"[serve] ok: {len(out.done)} requests, {toks} tokens, logits "
        f"finite, max |engine - dense prefill| {worst:.4f} (tol {PATH_TOL}), "
        f"max relative L2 {rel:.2e} over {len(seen)} prompt lengths")
    log(f"[serve] {meter} peak_bytes_in_use {peaks(jax, [dev])} "
        f"smoke_wall_s={wall:.1f} (smoke time, not a benchmark)")
    # the plain engine shares the weights; the probe's pool goes first
    want = {r.rid: r.out_tokens for r in out.done}
    plan, params = out.plan, out.engine.params
    out.engine.shutdown()
    del out
    gc.collect()
    plain = ServeEngine.from_plan(plan, params, arch=arch, mesh=mesh,
                                  seed=args.seed)
    for p in prompts:
        plain.submit(p, max_new_tokens=NEW_TOKENS)
    got = {r.rid: r.out_tokens for r in plain.run_until_idle(1000)}
    check(got == want, ("batched sampling differs from the probe's",
                        got, want))
    ps = plain.pressure_stats()
    check((ps["sampled_batched"], ps["sampled_per_slot"]) == (toks, 0), ps)
    log(f"[serve] ok: plain engine, {toks} tokens sampled in one program "
        "a tick, identical to the probe's")
    plain.shutdown()


def phase_train(args, jax, meter, dev):
    from repro.configs import ShapeConfig, run_arch
    from repro.core.describe import global_layer_mask
    from repro.launch.mesh import make_mesh
    from repro.launch.train import train

    arch, changed = run_arch(TRAIN_ARCH, layers=TRAIN_LAYERS)
    mask = global_layer_mask(arch)
    check(any(mask) and not all(mask), mask)   # global and windowed layers
    log(f"[train] arch {arch.name} changed from published: {changed} "
        f"global layers {[i for i, g in enumerate(mask) if g]}")
    shape = ShapeConfig("smoke_train", "train", TRAIN_SEQ, 1)
    meter.reset()
    t0 = time.perf_counter()
    tr, _, _ = train(arch, shape, steps=3, seed=args.seed, log_every=1,
                     mesh=make_mesh((1, 1), ("data", "model"),
                                    devices=[dev]),
                     ckpt_dir=str(HERE / ".smoke" / "train"))
    wall = time.perf_counter() - t0
    losses = [h["loss"] for h in tr.history]
    log(f"[train] plan={tr.plan.content_hash()[:12]} "
        f"strategy={tr.plan.estimates.get('strategy')} "
        f"remat={tr.plan.comm.remat_policy} losses={losses}")
    check(len(losses) == 3 and all(math.isfinite(x) for x in losses), losses)
    lnv = math.log(arch.vocab_size)
    check(abs(losses[0] - lnv) <= 0.05 * lnv, (losses[0], lnv))
    log(f"[train] ok: finite losses, step-0 loss {losses[0]:.4f} vs "
        f"ln(vocab) {lnv:.4f}")
    log(f"[train] {meter} peak_bytes_in_use {peaks(jax, [dev])} "
        f"(since process start) smoke_wall_s={wall:.1f} "
        "(smoke time, not a benchmark)")


def phase_serve4(args, jax, meter, devs):
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import run_arch
    from repro.launch.mesh import make_mesh
    from repro.launch.serve import make_prompts, serve
    from repro.serve.engine import ServeEngine

    arch, changed = run_arch("hymba-1.5b")
    log(f"[serve4] arch {arch.name} changed from published: {changed}")
    rng = np.random.default_rng(args.seed)
    lens = rng.choice(PROMPT_LENS, SERVE_BATCH)
    prompts = make_prompts(arch.vocab_size, lens, args.seed)
    probe = probe_engine(ServeEngine, np, jnp)
    kw = dict(new_tokens=NEW_TOKENS, max_batch=SERVE_BATCH,
              max_len=SERVE_LEN, seed=args.seed, engine_cls=probe)
    meter.reset()
    t0 = time.perf_counter()
    four = serve(arch, prompts, mesh=make_mesh((2, 2), ("data", "model"),
                                               devices=devs), **kw)
    log(f"[serve4] 2x2: {' '.join(f'{k}={v}' for k, v in four.summary().items())}"
        f" combine={four.engine.combine_topology}")
    check(four.engine.decode_path == "shard_map_flash_paged_2d",
          four.engine.decode_path)
    check_served(four, SERVE_BATCH, arch.vocab_size, np)
    one = serve(arch, prompts, mesh=make_mesh((1, 1), ("data", "model"),
                                              devices=devs[:1]),
                plan=four.plan, **kw)
    log(f"[serve4] 1 device: "
        f"{' '.join(f'{k}={v}' for k, v in one.summary().items())}")
    check_served(one, SERVE_BATCH, arch.vocab_size, np)
    wall = time.perf_counter() - t0
    got = {r.rid: r for r in four.done}
    diffs, flips = [], []          # flips: (rid, step, runner-up gap)
    for r1 in one.done:
        r4 = got[r1.rid]
        rows1, rows4 = one.engine.rows[r1.rid], four.engine.rows[r1.rid]
        for i, (t1, t4) in enumerate(zip(r1.out_tokens, r4.out_tokens)):
            diffs.append(float(np.abs(rows1[i] - rows4[i]).max()))
            check(diffs[-1] <= PATH_TOL, (r1.rid, i, diffs[-1]))
            if t1 != t4:
                gap = float(rows1[i][t1] - rows1[i][t4])
                check(0.0 <= gap <= NEAR_TIE, (r1.rid, i, t1, t4, gap))
                flips.append((r1.rid, i, round(gap, 4)))
                break              # prefixes differ from here on
    # how close the greedy choice usually is: random weights give flat
    # logits, so a flip needs only a small rounding difference
    top2 = np.median([np.subtract(*np.partition(row, -2)[:-3:-1])
                      for rows in one.engine.rows.values() for row in rows])
    log(f"[serve4] ok: 2x2 vs 1 device over {len(diffs)} steps: |logit "
        f"diff| max {max(diffs):.4f} median {np.median(diffs):.4f} (tol "
        f"{PATH_TOL}); near-tie flips (rid, step, gap) {flips} of "
        f"{len(one.done)} requests; median top-2 gap {top2:.4f}")
    log(f"[serve4] {meter} peak_bytes_in_use {peaks(jax, devs)} "
        f"smoke_wall_s={wall:.1f} (smoke time, not a benchmark)")
    four.engine.shutdown()
    one.engine.shutdown()


def phase_train4(args, jax, meter, devs):
    from repro.configs import ShapeConfig, run_arch
    from repro.launch.mesh import make_mesh
    from repro.launch.train import train

    arch, changed = run_arch(TRAIN_ARCH, layers=TRAIN_LAYERS)
    log(f"[train4] arch {arch.name} changed from published: {changed}")
    shape = ShapeConfig("smoke_wire", "train", TRAIN_SEQ, len(devs))
    mesh = make_mesh((len(devs), 1), ("data", "model"), devices=devs)
    meter.reset()
    t0 = time.perf_counter()
    losses = {}
    for gc_mode in ("on", "off"):
        tr, _, _ = train(arch, shape, mesh=mesh, steps=4, seed=args.seed,
                         log_every=1, grad_compression=gc_mode,
                         ckpt_dir=str(HERE / ".smoke" / f"wire_{gc_mode}"))
        check(tr.plan.comm.compress_lowered == (gc_mode == "on"), gc_mode)
        losses[gc_mode] = [h["loss"] for h in tr.history]
        log(f"[train4] wire {'int8' if gc_mode == 'on' else 'fp32'}: "
            f"plan={tr.plan.content_hash()[:12]} losses={losses[gc_mode]}")
        del tr
        gc.collect()
    wall = time.perf_counter() - t0
    on, off = losses["on"], losses["off"]
    check(all(math.isfinite(x) for x in on + off), losses)
    check(on[0] == off[0], (on[0], off[0]))
    gap = max(abs(a - b) for a, b in zip(on, off))
    check(gap <= WIRE_GAP, gap)
    log(f"[train4] ok: step-0 identical, max int8-vs-fp32 loss gap "
        f"{gap:.3e} (bound {WIRE_GAP})")
    log(f"[train4] {meter} peak_bytes_in_use {peaks(jax, devs)} "
        f"smoke_wall_s={wall:.1f} (smoke time, not a benchmark)")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    if not (HERE / "src" / "repro").is_dir():
        print(f"chip_smoke: no repository beside {HERE} (src/repro "
              "missing)", file=sys.stderr)
        return 2
    # every artifact of the run lives in the checkout and starts empty:
    # plans are specialized afresh, never read from an earlier store
    shutil.rmtree(HERE / ".smoke", ignore_errors=True)
    os.environ["REPRO_PLAN_DIR"] = str(HERE / ".smoke" / "plans")
    sys.path.insert(0, str(HERE / "src"))
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devs[0].platform!r})", file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devs)} device(s)",
              file=sys.stderr)
        return 1
    from repro.launch.env import enable_compile_cache, plan_target

    log(f"device {devs[0].device_kind} x{len(devs)} -> target "
        f"{plan_target()}, compile cache {enable_compile_cache()}")
    meter = CompileMeter(jax)
    if args.chips == 1:
        for arch, changed in serve_archs():
            phase_serve(args, jax, meter, devs[0], arch, changed)
            gc.collect()
        phase_train(args, jax, meter, devs[0])
    else:
        devs = devs[:4]
        phase_serve4(args, jax, meter, devs)
        gc.collect()
        phase_train4(args, jax, meter, devs)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
