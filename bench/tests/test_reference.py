"""The float32 references against the program, on the CPU at tiny
widths: prefill then paged decode through ``ServeEngine`` (one request
admitted on an aliased system prompt)."""

import numpy as np

import run
import tiny


class _Meter:
    s = 0.0


def _ctx(cell, tmp_path, seed=5):
    import jax
    spec = tiny.spec(cell)
    return run.Ctx(spec, seed, 1.0, False, jax.devices(), _Meter(), tmp_path)


def test_engine_logits_match_qwen3_reference(tmp_path):
    import jax.numpy as jnp
    import serve_cell
    from reference import qwen3
    from repro.serve.engine import ServeEngine

    rows = {}
    orig = ServeEngine._sample

    def probe(self, r, logits, key):
        rows.setdefault(r.rid, []).append(
            np.asarray(logits[:self.arch.vocab_size].astype(jnp.float32)))
        return orig(self, r, logits, key)

    ctx = _ctx("serve.qwen3-8b.chat", tmp_path)
    st = serve_cell.setup(ctx)
    eng = st["engine"]
    eng._sample = probe.__get__(eng)
    rng = np.random.default_rng(0)
    V = ctx.arch.vocab_size
    system = rng.integers(0, V, 32, dtype=np.int32)
    prompts = [np.concatenate([system, rng.integers(0, V, 9, dtype=np.int32)]),
               np.concatenate([system, rng.integers(0, V, 20, dtype=np.int32)]),
               rng.integers(0, V, 13, dtype=np.int32)]
    hits0 = eng.pressure_stats()["prefix_hit_tokens"]
    rids = [eng.submit(prompts[0], max_new_tokens=6)]
    eng.step()                          # the system prompt is now resident
    rids += [eng.submit(p, max_new_tokens=6) for p in prompts[1:]]
    while eng.pending or eng.active:
        eng.step()
    assert eng.pressure_stats()["prefix_hit_tokens"] - hits0 >= 32
    done = {r.rid: r for r in eng.finished}
    seqs, at = [], []
    for rid, p in zip(rids, prompts):
        out = done[rid].out_tokens
        seqs.append(np.concatenate([p, np.asarray(out[:-1], np.int32)]))
        at.append(np.arange(len(p) - 1, len(p) - 1 + len(out)))
    ref = qwen3.served_logits(ctx.config["model"], ctx.seed, seqs, at)
    for rid, r in zip(rids, ref):
        got = np.stack(rows[rid])
        scale = np.abs(r).max()
        # bf16 weights are shared; bf16 activations and logits are not
        assert np.abs(got - r).max() <= 0.02 * scale, rid

