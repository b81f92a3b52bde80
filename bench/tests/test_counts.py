"""Model operations and bytes from shapes: a hand count at the published
Qwen3-8B shape, and the compiled program's own dot count at a tiny
shape without remat."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import counts
import run

QWEN = json.load(open(run.BENCH / "configs" / "qwen3-8b.l16.json"))["model"]


def test_hand_count_qwen3_layer():
    # q, o: 4096x4096; k, v: 4096x1024; gate, up, down: 4096x12288
    per_layer = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 12288
    assert counts.layer_matmul_params(QWEN) == per_layer
    # one token at position 0: 2 FLOP per weight, 4*H*hd per key seen
    one = counts.prefill_flops(QWEN, 0, 1)
    assert one == 16 * (2 * per_layer + 4 * 32 * 128) + 2 * 4096 * 151936
    # decode of two slots at 100 and 300 cached positions (after append)
    d = counts.decode_flops(QWEN, [100, 300])
    assert d == 2 * (16 * 2 * per_layer + 2 * 4096 * 151936) \
        + 16 * 4 * 32 * 128 * 400
    kv = 2 * 16 * 8 * 128 * 2
    assert counts.kv_bytes_per_token(QWEN) == kv == 65536
    assert counts.decode_bytes(QWEN, [100, 300]) == \
        16 * per_layer * 2 + 4096 * 151936 * 2 + 2 * 4096 * 2 + kv * 400


def test_matches_the_compiled_dots_without_remat():
    """The program computes every query block against all keys (masked),
    so its dot count equals the model count with each position seeing
    the whole sequence; nothing else differs at this shape."""
    from repro.analysis import hlo_stats
    from repro.configs.base import ArchConfig
    from repro.models import lm
    cfg = dict(QWEN, n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
               head_dim=16, d_ff=128, vocab_size=256)
    fields = {f for f in ArchConfig.__dataclass_fields__}
    arch = ArchConfig(**{k: v for k, v in cfg.items() if k in fields})
    params = lm.init_params(arch, jax.random.PRNGKey(0))
    S = 32
    rc = lm.RunCfg(block_q=S)

    def fwd(p, t):
        x, _ = lm.forward(arch, p, {"tokens": t}, rc)
        return lm._logits(arch, p, x, rc)

    text = jax.jit(fwd).lower(params, jnp.zeros((1, S), jnp.int32)) \
        .compile().as_text()
    hlo = hlo_stats.parse_hlo(text, 1)["flops"]
    full = counts.forward_flops(cfg, np.full(S, S - 1), S)
    assert hlo == pytest.approx(full, rel=1e-6)
