"""Model assembly: all ten architectures behind one functional interface.

Structure
---------
Parameters are nested dicts of arrays, *stacked over scan groups*:
``lax.scan`` over layers keeps the HLO size O(1) in depth (an 80-layer
72B model lowers in seconds).  Architectures with interleaved layer kinds
(llama4: dense/MoE alternation) scan over super-blocks of
``moe_interleave`` layers; hymba passes per-layer window sizes as scan
inputs so global/sliding layers share one body.

Entry points (all pure):
  * ``init_params(arch, key, ...)``
  * ``param_specs(arch, ...)``        — ShapeDtypeStructs + logical axes
  * ``train_loss(arch, params, batch, cfg)``
  * ``prefill(arch, params, batch, cfg)``  -> (logits_last, cache)
  * ``decode_step(arch, params, cache, batch, cfg)`` -> (logits, cache)

The model is *mostly unaware* of the memory plan (paper §4): it consumes
only a tiny ``RunCfg`` of lowering-relevant knobs that the plan's
lowering pass fills in (block sizes, remat policy, moe path, padded
vocab).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core.describe import global_layer_mask
from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.attention import AttnParams, MLAParams
from repro.models.common import (
    cross_entropy_loss,
    rms_norm,
    sinusoidal_positions,
    truncated_normal_init,
)
from repro.models.moe import MoEParams
from repro.models.ssm import SSMDims, SSMParams


@dataclasses.dataclass(frozen=True)
class RunCfg:
    """Plan-derived lowering knobs (filled by core.passes.lowering)."""

    vocab_padded: int = 0          # 0 -> arch.vocab_size
    heads_padded: int = 0          # 0 -> arch.n_heads (layout pass pads to TP)
    kv_heads_padded: int = 0       # 0 -> arch.n_kv_heads
    ssm_heads_padded: int = 0      # 0 -> arch.ssm_heads
    kv_heads_sharded: bool = True  # False -> constrain k/v replicated on TP
    shard_heads: bool = True       # False (fsdp_dp): no head constraints
    block_q: int = 512             # attention query tile
    ssd_chunk: int = 256           # SSD chunk length
    remat: str = "none"            # none | dots | full
    moe_impl: str = "gshard_einsum"  # or shard_map_alltoall | dense_einsum
    decode_impl: str = "xla"       # or shard_map_flash (seq-sharded cache)
    combine_topology: Optional[str] = None  # flat|ring|bidir; None -> predicate
    mesh: Optional[jax.sharding.Mesh] = None   # needed by shard_map path
    data_axes: Tuple[str, ...] = ("data",)
    model_axis: str = "model"
    batch_spec: Any = None         # resolved batch-dim mesh assignment
    aux_loss_weight: float = 0.01


_U = jax.sharding.PartitionSpec.UNCONSTRAINED


def _hint(x, cfg: "RunCfg", *spec):
    """with_sharding_constraint helper.

    spec entries: mesh-axis name (shard), "rep" (force replicated), or
    None (leave unconstrained).  No-op without a mesh (smoke tests).
    """
    if cfg.mesh is None:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P
    full = tuple(None if s == "rep" else (_U if s is None else s)
                 for s in spec)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(cfg.mesh, P(*full)))


# =====================================================================
# Parameter specs
# =====================================================================

class LeafSpec(NamedTuple):
    shape: Tuple[int, ...]
    dtype: str
    axes: Tuple[Optional[str], ...]
    scale: float = 0.02


def _attn_specs(arch: ArchConfig, Lg: int, d: int,
                heads_padded: int = 0,
                kv_heads_padded: int = 0) -> Dict[str, Any]:
    if arch.is_mla:
        return _mla_specs(arch, Lg, d)
    hd = arch.hd
    H = heads_padded or arch.n_heads
    K = kv_heads_padded or arch.n_kv_heads
    specs = {
        "wq": LeafSpec((Lg, d, H * hd), "bfloat16",
                       ("layers", "embed", "heads")),
        "wk": LeafSpec((Lg, d, K * hd), "bfloat16",
                       ("layers", "embed", "kv_heads")),
        "wv": LeafSpec((Lg, d, K * hd), "bfloat16",
                       ("layers", "embed", "kv_heads")),
        "wo": LeafSpec((Lg, H * hd, d), "bfloat16",
                       ("layers", "heads", "embed"),
                       scale=0.02 / math.sqrt(2 * arch.n_layers)),
    }
    if arch.qk_norm:
        specs["q_norm"] = LeafSpec((Lg, hd), "float32", ("layers", None), 0.0)
        specs["k_norm"] = LeafSpec((Lg, hd), "float32", ("layers", None), 0.0)
    return specs


def _mla_specs(arch: ArchConfig, Lg: int, d: int) -> Dict[str, Any]:
    H, r = arch.n_heads, arch.kv_lora_rank
    nope, rope, dv = (arch.qk_nope_head_dim, arch.qk_rope_head_dim,
                      arch.v_head_dim)
    return {
        "wq": LeafSpec((Lg, d, H * (nope + rope)), "bfloat16",
                       ("layers", "embed", "heads")),
        "wkv_a": LeafSpec((Lg, d, r + rope), "bfloat16",
                          ("layers", "embed", None)),
        # the leaf's last name is "norm": a norm scale, offset from 1
        "kv_norm": {"norm": LeafSpec((Lg, r), "float32",
                                     ("layers", None), 0.0)},
        "wkv_b": LeafSpec((Lg, r, H * (nope + dv)), "bfloat16",
                          ("layers", None, "heads")),
        "wo": LeafSpec((Lg, H * dv, d), "bfloat16",
                       ("layers", "heads", "embed"),
                       scale=0.02 / math.sqrt(2 * arch.n_layers)),
    }


def _mlp_specs(arch: ArchConfig, Lg: int, d: int, ff: int) -> Dict[str, LeafSpec]:
    gated = arch.gated_mlp and arch.family != "encoder"
    n_in = 2 if gated else 1
    return {
        "wi": LeafSpec((Lg, d, n_in * ff), "bfloat16", ("layers", "embed", "ff")),
        "wo": LeafSpec((Lg, ff, d), "bfloat16", ("layers", "ff", "embed"),
                       scale=0.02 / math.sqrt(2 * arch.n_layers)),
    }


def _moe_specs(arch: ArchConfig, Lg: int, d: int) -> Dict[str, LeafSpec]:
    ff = arch.moe_d_ff or arch.d_ff
    E, Eh = arch.n_experts, arch.experts_held
    specs = {
        "router": LeafSpec((Lg, d, E), "float32", ("layers", "embed", None)),
        "wi": LeafSpec((Lg, Eh, d, 2 * ff), "bfloat16",
                       ("layers", "experts", "embed", "ff")),
        "wo": LeafSpec((Lg, Eh, ff, d), "bfloat16",
                       ("layers", "experts", "ff", "embed"),
                       scale=0.02 / math.sqrt(2 * arch.n_layers)),
    }
    if arch.n_shared_experts:
        sf = ff * arch.n_shared_experts
        specs["shared_wi"] = LeafSpec((Lg, d, 2 * sf), "bfloat16",
                                      ("layers", "embed", "ff"))
        specs["shared_wo"] = LeafSpec((Lg, sf, d), "bfloat16",
                                      ("layers", "ff", "embed"),
                                      scale=0.02 / math.sqrt(2 * arch.n_layers))
    return specs


def _ssm_specs(arch: ArchConfig, Lg: int, d: int,
               ssm_heads_padded: int = 0) -> Dict[str, LeafSpec]:
    H = ssm_heads_padded or arch.ssm_heads
    di = H * arch.ssm_head_dim
    G, N = arch.ssm_n_groups, arch.ssm_state
    cdim = di + 2 * G * N
    return {
        "in_proj": LeafSpec((Lg, d, 2 * di + 2 * G * N + H), "bfloat16",
                            ("layers", "embed", "ssm_inner")),
        "conv_w": LeafSpec((Lg, arch.ssm_conv, cdim), "bfloat16",
                           ("layers", None, "ssm_inner")),
        "conv_b": LeafSpec((Lg, cdim), "bfloat16", ("layers", "ssm_inner"), 0.0),
        "A_log": LeafSpec((Lg, H), "float32", ("layers", "ssm_heads"), 0.0),
        "D": LeafSpec((Lg, H), "float32", ("layers", "ssm_heads"), 0.0),
        "dt_bias": LeafSpec((Lg, H), "float32", ("layers", "ssm_heads"), 0.0),
        "norm": LeafSpec((Lg, di), "float32", ("layers", "ssm_inner"), 0.0),
        "out_proj": LeafSpec((Lg, di, d), "bfloat16",
                             ("layers", "ssm_inner", "embed"),
                             scale=0.02 / math.sqrt(2 * arch.n_layers)),
    }


def leaf_specs(arch: ArchConfig, vocab_padded: int = 0,
               heads_padded: int = 0,
               ssm_heads_padded: int = 0,
               kv_heads_padded: int = 0) -> Dict[str, Any]:
    """The full parameter-spec pytree for an architecture."""
    d = arch.d_model
    V = vocab_padded or arch.vocab_size
    L = arch.n_layers - arch.first_dense_layers
    g = arch.moe_interleave if arch.is_moe and arch.moe_interleave > 1 else 1
    Lg = L // g

    specs: Dict[str, Any] = {
        "embed": LeafSpec((V, d), "bfloat16", ("vocab", "embed"), 0.02),
        "final_norm": LeafSpec((d,), "float32", ("embed",), 0.0),
    }
    if not arch.tie_embeddings:
        specs["lm_head"] = LeafSpec((d, V), "bfloat16", ("embed", "vocab"))

    blocks: Dict[str, Any] = {}

    def mixer_specs(Lh: int) -> Dict[str, Any]:
        out: Dict[str, Any] = {"pre_norm": LeafSpec((Lh, d), "float32",
                                                    ("layers", "embed"), 0.0)}
        if arch.has_attention:
            out["attn"] = _attn_specs(arch, Lh, d, heads_padded,
                                      kv_heads_padded)
        if arch.has_ssm:
            out["ssm"] = _ssm_specs(arch, Lh, d, ssm_heads_padded)
        return out

    if arch.family == "ssm":
        blocks.update(mixer_specs(Lg))
    elif arch.is_moe and g > 1:
        # llama4: [dense, moe] super-block
        blocks["dense"] = {**mixer_specs(Lg),
                           "mlp_norm": LeafSpec((Lg, d), "float32",
                                                ("layers", "embed"), 0.0),
                           "mlp": _mlp_specs(arch, Lg, d, arch.d_ff)}
        blocks["moe"] = {**mixer_specs(Lg),
                         "mlp_norm": LeafSpec((Lg, d), "float32",
                                              ("layers", "embed"), 0.0),
                         "moe": _moe_specs(arch, Lg, d)}
    elif arch.is_moe:
        blocks.update(mixer_specs(Lg))
        blocks["mlp_norm"] = LeafSpec((Lg, d), "float32", ("layers", "embed"), 0.0)
        blocks["moe"] = _moe_specs(arch, Lg, d)
        Ld = arch.first_dense_layers
        if Ld:
            # deepseek: leading dense layers, a stack of their own that
            # runs before the MoE stack
            specs["lead"] = {**mixer_specs(Ld),
                             "mlp_norm": LeafSpec((Ld, d), "float32",
                                                  ("layers", "embed"), 0.0),
                             "mlp": _mlp_specs(arch, Ld, d, arch.d_ff)}
    else:
        blocks.update(mixer_specs(Lg))
        blocks["mlp_norm"] = LeafSpec((Lg, d), "float32", ("layers", "embed"), 0.0)
        blocks["mlp"] = _mlp_specs(arch, Lg, d, arch.d_ff)

    specs["blocks"] = blocks
    return specs


def param_shapes(arch: ArchConfig, vocab_padded: int = 0,
                 heads_padded: int = 0, ssm_heads_padded: int = 0,
                 kv_heads_padded: int = 0):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.dtype(s.dtype)),
        leaf_specs(arch, vocab_padded, heads_padded, ssm_heads_padded,
                   kv_heads_padded),
        is_leaf=lambda x: isinstance(x, LeafSpec))


def param_axes(arch: ArchConfig, vocab_padded: int = 0,
               heads_padded: int = 0, ssm_heads_padded: int = 0,
               kv_heads_padded: int = 0):
    return jax.tree.map(
        lambda s: s.axes,
        leaf_specs(arch, vocab_padded, heads_padded, ssm_heads_padded,
                   kv_heads_padded),
        is_leaf=lambda x: isinstance(x, LeafSpec))


def init_params(arch: ArchConfig, key: jax.Array, vocab_padded: int = 0,
                heads_padded: int = 0, ssm_heads_padded: int = 0,
                kv_heads_padded: int = 0):
    specs = leaf_specs(arch, vocab_padded, heads_padded, ssm_heads_padded,
                       kv_heads_padded)
    leaves, treedef = jax.tree.flatten(
        specs, is_leaf=lambda x: isinstance(x, LeafSpec))
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, s in zip(keys, leaves):
        if s.scale == 0.0:
            out.append(jnp.zeros(s.shape, jnp.dtype(s.dtype)))
        else:
            out.append(truncated_normal_init(k, s.shape, jnp.dtype(s.dtype),
                                             s.scale))
    params = jax.tree.unflatten(treedef, out)
    # dead (layout-pass padded) q/kv heads: zero padded wq/wk/wv cols,
    # wo rows — they contribute nothing at init
    if arch.has_attention and heads_padded and heads_padded != arch.n_heads:
        cut = arch.n_heads * arch.hd
        for grp in _mixer_groups(params):
            if "attn" in grp:
                grp["attn"]["wq"] = grp["attn"]["wq"].at[..., cut:].set(0)
                grp["attn"]["wo"] = grp["attn"]["wo"].at[:, cut:, :].set(0)
    if arch.has_attention and kv_heads_padded and             kv_heads_padded != arch.n_kv_heads:
        cut = arch.n_kv_heads * arch.hd
        for grp in _mixer_groups(params):
            if "attn" in grp:
                grp["attn"]["wk"] = grp["attn"]["wk"].at[..., cut:].set(0)
                grp["attn"]["wv"] = grp["attn"]["wv"].at[..., cut:].set(0)
    # SSM: A_log ~ log(uniform[1,16]), dt_bias ~ inv_softplus(uniform)
    def fix_ssm(p):
        if arch.has_ssm:
            for grp in _mixer_groups(p):
                if "ssm" in grp:
                    Lh, H = grp["ssm"]["A_log"].shape
                    a = jnp.log(jnp.linspace(1.0, 16.0, H))[None, :]
                    grp["ssm"]["A_log"] = jnp.broadcast_to(a, (Lh, H)).astype(
                        jnp.float32)
                    grp["ssm"]["D"] = jnp.ones((Lh, H), jnp.float32)
                    grp["ssm"]["dt_bias"] = jnp.full((Lh, H), -2.0, jnp.float32)
        return p
    return fix_ssm(params)


def _mixer_groups(params):
    b = params["blocks"]
    if "dense" in b and isinstance(b["dense"], dict):
        return [b["dense"], b["moe"]]
    return [b] + ([params["lead"]] if "lead" in params else [])


# =====================================================================
# Forward pass
# =====================================================================

def _embed_in(arch, params, batch, cfg):
    """Returns (x (B,S,d) bf16, positions, mask_positions (B,S))."""
    if "embeds" in batch:
        x = batch["embeds"].astype(jnp.bfloat16)
        B, S = x.shape[:2]
        if arch.modality == "audio":
            x = x + sinusoidal_positions(S, arch.d_model)[None].astype(x.dtype)
    else:
        tokens = batch["tokens"]
        B, S = tokens.shape
        x = jnp.take(params["embed"], tokens, axis=0)
    if "positions" in batch:
        positions = batch["positions"]              # (3,B,S) mrope or (B,S)
        mask_pos = positions[0] if positions.ndim == 3 else positions
    else:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        if arch.mrope_sections is not None:
            positions = jnp.broadcast_to(positions, (3, B, S))
            mask_pos = positions[0]
        else:
            mask_pos = positions
    return x, positions, mask_pos


def _logits(arch, params, x, cfg):
    # stays bf16 (and vocab-sharded); CE/sampling upcast inside fused
    # reductions so the fp32 full-vocab tensor never hits HBM
    w = params["embed"].T if arch.tie_embeddings else params["lm_head"]
    return x @ w


def _ssm_dims(arch: ArchConfig, sp: SSMParams = None) -> SSMDims:
    if sp is not None:  # padding-aware: heads from A_log, di from out_proj
        H = sp.A_log.shape[-1]
        di = sp.out_proj.shape[-2]
        return SSMDims(arch.d_model, di, H, di // H, arch.ssm_state,
                       arch.ssm_n_groups, arch.ssm_conv)
    return SSMDims(arch.d_model, arch.d_inner, arch.ssm_heads,
                   arch.ssm_head_dim, arch.ssm_state, arch.ssm_n_groups,
                   arch.ssm_conv)


def _attn_params(grp) -> AttnParams:
    a = grp["attn"]
    return AttnParams(a["wq"], a["wk"], a["wv"], a["wo"], a.get("q_norm"),
                      a.get("k_norm"))


def _mla_params(grp) -> MLAParams:
    a = grp["attn"]
    return MLAParams(a["wq"], a["wkv_a"], a["kv_norm"]["norm"], a["wkv_b"],
                     a["wo"])


def _self_attention(arch, cfg, grp, h, positions, mask_pos, window,
                    prefix=None, q_pos=None, k_pos=None):
    """Prefill-form attention of one layer over ``h`` (B,S,d).

    With ``prefix`` (``{pool: (B,M,...)}``: the cache rows of M earlier
    positions) the S queries sit at ``q_pos`` after them and attend over
    prefix-plus-own keys through :func:`attention_tail`.  Returns the
    layer's output and ``{pool: (B,S,...)}``, the rows its cache gains:
    post-RoPE ``k``/``v`` per kv head, or one latent ``ckv`` row (MLA)."""
    B, S = h.shape[:2]
    if arch.is_mla:
        p = _mla_params(grp)
        q_nope, q_pe, row = attn_mod.mla_project(h, p, arch, positions)
        full = row if prefix is None else jnp.concatenate(
            [prefix["ckv"].astype(row.dtype), row], axis=1)
        k, v = attn_mod.mla_expand(full, p, arch)
        q = jnp.concatenate([q_nope, q_pe], axis=-1)
        scale = attn_mod.mla_softmax_scale(arch)
        if prefix is None:
            ctx = attn_mod.attention_chunked(
                q, k, v, causal=arch.causal, window=window,
                block_q=cfg.block_q, positions=mask_pos, scale=scale)
        else:
            ctx = attn_mod.attention_tail(
                q, k, v, q_positions=q_pos, k_positions=k_pos,
                causal=arch.causal, window=window, block_q=cfg.block_q,
                scale=scale)
        return ctx.reshape(B, S, -1) @ p.wo, {"ckv": row}
    ap = _attn_params(grp)
    Hq = ap.wq.shape[-1] // arch.hd        # layout pass may pad heads
    q, k, v = attn_mod.project_qkv(
        h, ap, Hq, ap.wk.shape[-1] // arch.hd, arch.hd, positions,
        arch.rope_theta, arch.mrope_sections, arch.norm_eps)
    if cfg.shard_heads:
        q = _hint(q, cfg, None, None, cfg.model_axis, None)
        kv_spec = cfg.model_axis if cfg.kv_heads_sharded else "rep"
        k = _hint(k, cfg, None, None, kv_spec, None)
        v = _hint(v, cfg, None, None, kv_spec, None)
    if prefix is None:
        ctx = attn_mod.attention_chunked(
            q, k, v, causal=arch.causal, window=window,
            block_q=cfg.block_q, positions=mask_pos)
    else:
        k_full = jnp.concatenate([prefix["k"].astype(k.dtype), k], axis=1)
        v_full = jnp.concatenate([prefix["v"].astype(v.dtype), v], axis=1)
        ctx = attn_mod.attention_tail(
            q, k_full, v_full, q_positions=q_pos, k_positions=k_pos,
            causal=arch.causal, window=window, block_q=cfg.block_q)
    return ctx.reshape(B, S, -1) @ ap.wo, {"k": k, "v": v}


def _mixer_fwd(arch, cfg, grp, x, positions, mask_pos, window):
    """Pre-norm mixer: attention and/or SSM paths (parallel for hybrid)."""
    h = rms_norm(x, grp["pre_norm"], arch.norm_eps)
    out = 0.0
    n_paths = int(arch.has_attention) + int(arch.has_ssm)
    if arch.has_attention:
        y, _ = _self_attention(arch, cfg, grp, h, positions, mask_pos, window)
        out = out + y
    if arch.has_ssm:
        sp = SSMParams(**grp["ssm"])
        out = out + ssm_mod.ssm_forward(h, sp, _ssm_dims(arch, sp),
                                        chunk=cfg.ssd_chunk)
    return x + out / n_paths


def _counts_pairs(cfg) -> bool:
    """Does the FFN report held token-expert pairs (the held layer)?"""
    return cfg.moe_impl == "held_dropless"


def _ffn_fwd(arch, cfg, grp, x):
    """Pre-norm FFN: dense MLP or MoE. Returns (x, aux_loss, held):
    ``held`` (B,S) counts each token's pairs on held experts under the
    held-expert layer, and is None elsewhere."""
    aux = jnp.zeros((), jnp.float32)
    held = None
    if "mlp" in grp:
        h = rms_norm(x, grp["mlp_norm"], arch.norm_eps)
        wi, wo = grp["mlp"]["wi"], grp["mlp"]["wo"]
        gated = arch.gated_mlp and arch.family != "encoder"
        z = h @ wi
        if gated:
            g, u = jnp.split(z, 2, axis=-1)
            z = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
        elif arch.family == "encoder":
            z = jax.nn.gelu(z.astype(jnp.float32)).astype(x.dtype)
        else:  # squared relu (minitron/nemotron)
            z = jnp.square(jax.nn.relu(z.astype(jnp.float32))).astype(x.dtype)
        x = x + z @ wo
        if _counts_pairs(cfg):
            held = jnp.zeros(x.shape[:2], jnp.int32)
    elif "moe" in grp:
        h = rms_norm(x, grp["mlp_norm"], arch.norm_eps)
        mp = MoEParams(grp["moe"]["router"], grp["moe"]["wi"], grp["moe"]["wo"],
                       grp["moe"].get("shared_wi"), grp["moe"].get("shared_wo"))
        if cfg.moe_impl in ("dense_einsum", "held_dropless"):
            with jax.named_scope("moe"):
                y, aux, pairs = moe_mod.moe_dense_einsum(
                    h, mp, top_k=arch.experts_per_token)
            if _counts_pairs(cfg):
                held = pairs
        elif cfg.moe_impl == "shard_map_alltoall" and cfg.mesh is not None:
            y, aux = moe_mod.moe_shard_map(
                h, mp, top_k=arch.experts_per_token,
                capacity_factor=arch.capacity_factor, mesh=cfg.mesh,
                data_axes=cfg.data_axes, model_axis=cfg.model_axis)
        else:
            y, aux = moe_mod.moe_gshard_einsum(
                h, mp, top_k=arch.experts_per_token,
                capacity_factor=arch.capacity_factor)
        x = x + y
    return x, aux, held


def _block_fwd(arch, cfg, grp, x, positions, mask_pos, window):
    x = _mixer_fwd(arch, cfg, grp, x, positions, mask_pos, window)
    if arch.family == "ssm":
        return x, jnp.zeros((), jnp.float32)
    x, aux, _ = _ffn_fwd(arch, cfg, grp, x)
    return x, aux


def _remat(fn, policy: str):
    if policy == "none":
        return fn
    if policy in ("dots", "dots_saveable"):
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            prevent_cse=False)
    return jax.checkpoint(fn, prevent_cse=False)


def _window_schedule(arch: ArchConfig) -> jnp.ndarray:
    """(L,) per-layer attention window (0 = unlimited/global)."""
    mask = global_layer_mask(arch)
    return jnp.asarray(
        [0 if g else arch.window for g in mask], dtype=jnp.int32)


def _stacks(arch: ArchConfig, params):
    """The layer stacks a pass scans, in order, each with its per-layer
    attention windows: the leading dense layers (deepseek), then the
    main stack; llama4's super-blocks of ``g`` layers scan as one."""
    L = arch.n_layers
    w = _window_schedule(arch) if arch.has_attention \
        else jnp.zeros((L,), jnp.int32)
    g = arch.moe_interleave if arch.is_moe and arch.moe_interleave > 1 else 1
    if g > 1:
        return [(params["blocks"], w.reshape(L // g, g))]
    Ld = arch.first_dense_layers
    if not Ld:
        return [(params["blocks"], w)]
    return [(params["lead"], w[:Ld]), (params["blocks"], w[Ld:])]


def forward(arch: ArchConfig, params, batch, cfg: RunCfg):
    """Full-sequence forward -> (hidden (B,S,d), aux_loss)."""
    x, positions, mask_pos = _embed_in(arch, params, batch, cfg)
    # the embedding gather cannot carry both the batch sharding (indices)
    # and the table's feature sharding; pin the residual stream's batch dim
    # so GSPMD never replicates the activations (fsdp_dp strategy)
    if cfg.batch_spec is not None:
        x = _hint(x, cfg, cfg.batch_spec, None, "rep")
    g = arch.moe_interleave if arch.is_moe and arch.moe_interleave > 1 else 1

    def body(carry, xs):
        x, aux = carry
        if g > 1:
            grp_params, w = xs
            x = _mixer_fwd(arch, cfg, grp_params["dense"], x, positions,
                           mask_pos, w[0])
            x, a1, _ = _ffn_fwd(arch, cfg, grp_params["dense"], x)
            x = _mixer_fwd(arch, cfg, grp_params["moe"], x, positions,
                           mask_pos, w[1])
            x, a2, _ = _ffn_fwd(arch, cfg, grp_params["moe"], x)
            return (x, aux + a1 + a2), None
        grp_params, w = xs
        x, a = _block_fwd(arch, cfg, grp_params, x, positions, mask_pos, w)
        if cfg.batch_spec is not None:
            x = _hint(x, cfg, cfg.batch_spec, None, "rep")
        return (x, aux + a), None

    body = _remat(body, cfg.remat)
    carry = (x, jnp.zeros((), jnp.float32))
    for xs in _stacks(arch, params):
        carry, _ = jax.lax.scan(body, carry, xs)
    x, aux = carry
    x = rms_norm(x, params["final_norm"], arch.norm_eps)
    return x, aux


def train_loss(arch: ArchConfig, params, batch, cfg: RunCfg):
    """Scalar loss for one batch. batch: tokens/embeds, targets, [mask]."""
    x, aux = forward(arch, params, batch, cfg)
    logits = _logits(arch, params, x, cfg)
    loss, n = cross_entropy_loss(
        logits, batch["targets"], batch.get("mask"),
        vocab_size=arch.vocab_size)
    metrics = {"ce_loss": loss, "aux_loss": aux, "tokens": n}
    return loss + cfg.aux_loss_weight * aux, metrics


# =====================================================================
# Serving: prefill + decode
# =====================================================================

def cache_rows(arch: ArchConfig, kv_heads: int = 0
               ) -> Dict[str, Tuple[int, ...]]:
    """The attention cache's pools and the shape of one token's row in
    each, for one layer: ``k``/``v`` of every kv head, or MLA's one
    latent ``ckv`` row (``kv_lora_rank + qk_rope_head_dim`` values).

    This is the cache *kind* the memory template specializes: every
    dense stripe is ``(L, B, max_len, *row)``, every paged pool ``(L,
    n_blocks, block_len, *row)``, and the serving engine's block movers
    (spill, promote, copy-on-write, prefix alias, park) carry whatever
    pools this names."""
    if not arch.has_attention:
        return {}
    if arch.is_mla:
        return {"ckv": (arch.kv_row_width,)}
    K, hd = kv_heads or arch.n_kv_heads, arch.hd
    return {"k": (K, hd), "v": (K, hd)}


def init_cache(arch: ArchConfig, batch_size: int, max_len: int,
               dtype=jnp.bfloat16, ssm_heads: int = 0,
               kv_heads: int = 0) -> Dict[str, Any]:
    """Session state ("cache.kv" + SSM states in the template).

    ``pos`` is a per-slot ``(B,)`` vector: continuous batching mixes
    prompt lengths, so each batch entry appends and masks at its own
    offset (an engine-global scalar silently corrupts every slot whose
    length differs from the max).
    """
    L = arch.n_layers
    Hs = ssm_heads or arch.ssm_heads
    cache: Dict[str, Any] = {"pos": jnp.zeros((batch_size,), jnp.int32)}
    for key, row in cache_rows(arch, kv_heads).items():
        cache[key] = jnp.zeros((L, batch_size, max_len, *row), dtype)
    if arch.has_ssm:
        cache["ssm"] = jnp.zeros(
            (L, batch_size, Hs, arch.ssm_head_dim, arch.ssm_state),
            jnp.float32)
        cache["conv"] = jnp.zeros(
            (L, batch_size, arch.ssm_conv,
             Hs * arch.ssm_head_dim + 2 * arch.ssm_n_groups * arch.ssm_state),
            jnp.bfloat16)
    return cache


def init_paged_cache(arch: ArchConfig, batch_size: int, max_len: int,
                     block_len: int, n_blocks: int,
                     dtype=jnp.bfloat16, ssm_heads: int = 0,
                     kv_heads: int = 0) -> Dict[str, Any]:
    """Paged session state: the cache stripes become a block pool.

    Each pool of :func:`cache_rows` (``k``/``v``, or MLA's latent
    ``ckv``) is ``(L, n_blocks, block_len, *row)``, shared by all slots
    — one block id addresses the same block in every layer and pool —
    and ``block_tbl`` is the per-slot ``(B, ceil(max_len/block_len))``
    table mapping sequence positions to pool blocks (-1 = unassigned).
    SSM/conv states stay dense per-slot (they are O(1) in seq).  The
    geometry (block_len, n_blocks) is a plan decision
    (``DataOrganizationPass`` via ``costmodel.kv_block_geometry``);
    under 2-D pool sharding the block dim is additionally split
    data-major into per-data-shard sub-pools, and the allocator filling
    ``block_tbl`` must keep each slot's blocks inside the sub-pool of
    the data shard hosting it (``serve.allocator.BlockAllocator``) —
    the batch-partitioned ``flash_decode_paged`` combine masks out any
    block its data row does not own.
    """
    L = arch.n_layers
    Hs = ssm_heads or arch.ssm_heads
    nb = -(-max_len // block_len)
    cache: Dict[str, Any] = {"pos": jnp.zeros((batch_size,), jnp.int32)}
    for key, row in cache_rows(arch, kv_heads).items():
        cache[key] = jnp.zeros((L, n_blocks, block_len, *row), dtype)
    if arch.has_attention:
        cache["block_tbl"] = jnp.full((batch_size, nb), -1, jnp.int32)
    if arch.has_ssm:
        cache["ssm"] = jnp.zeros(
            (L, batch_size, Hs, arch.ssm_head_dim, arch.ssm_state),
            jnp.float32)
        cache["conv"] = jnp.zeros(
            (L, batch_size, arch.ssm_conv,
             Hs * arch.ssm_head_dim + 2 * arch.ssm_n_groups * arch.ssm_state),
            jnp.bfloat16)
    return cache


def init_host_pool(arch: ArchConfig, n_host_blocks: int, block_len: int,
                   dtype=jnp.bfloat16, kv_heads: int = 0
                   ) -> Dict[str, np.ndarray]:
    """Host-DRAM spill tier behind the paged pool (``kv_tier_split``).

    Same per-block row layout as the device pools — one ``(L,
    host_blocks, block_len, *row)`` array per pool of
    :func:`cache_rows` — but held as **numpy** arrays: host memory by
    construction, never part of a jit graph, so a spilled block costs
    HBM nothing.  Blocks migrate between the two pools with
    :func:`gather_blocks` / :func:`scatter_blocks` (one batched gather
    or scatter per transfer; the host->device leg is the
    ``jax.device_put`` the engine's prefetch stages a tick early).
    The dtype matches the device pool exactly (bf16 via ml_dtypes), so
    a spill/promote round trip is bit-identical — the token-identity
    tests lean on that.
    """
    L = arch.n_layers
    return {key: np.zeros((L, n_host_blocks, block_len, *row),
                          dtype=np.dtype(dtype))
            for key, row in cache_rows(arch, kv_heads).items()}


def gather_blocks(pool: jax.Array, ids: jax.Array) -> jax.Array:
    """Pull whole blocks out of a ``(L, n_blocks, block_len, *row)``
    pool as ``(L, len(ids), block_len, *row)`` rows — the device half
    of a block migration (spill reads, promote scatter-writes) and of a
    prefix alias.  One gather per layer: a gather across the layer axis
    makes the TPU compiler lay the whole pool out again first (a 4.8 GiB
    temporary for a 5.4 GB latent pool).  Jit-friendly: the engine wraps
    it once."""
    return jax.lax.map(lambda layer: layer[ids], pool)


def scatter_blocks(pool: jax.Array, ids: jax.Array,
                   rows: jax.Array) -> jax.Array:
    """Write whole blocks back into a pool — the inverse of
    :func:`gather_blocks`, one batched scatter."""
    return pool.at[:, ids].set(rows)


def append_kv_paged(pool: jax.Array, new: jax.Array, pos: jax.Array,
                    tbl: jax.Array, start=0) -> jax.Array:
    """Paged KV append: write ``new[b]`` into slot b's owning block.

    ``pool`` is ``(n_blocks, block_len, *row)``, ``new`` ``(B, 1,
    *row)``, ``pos`` ``(B,)`` dense-view offsets, ``tbl`` ``(B, nb)``.
    Slots whose owning table entry is unassigned (-1) are dropped — a
    freed slot's dummy decode never touches the pool.  ``start`` is the
    caller's first global block id when ``pool`` is one shard of a
    sharded pool (``dist.flash_decode.flash_decode_paged`` — under 2-D
    pool sharding the shard's offset linearizes its (data..., model)
    mesh coordinates data-major): blocks owned elsewhere are dropped
    too.  Oracle: :func:`repro.kernels.ref.paged_append_ref`.
    """
    N, bl = pool.shape[0], pool.shape[1]
    blk = jnp.take_along_axis(tbl, (pos // bl)[:, None], axis=1)[:, 0] - start
    # scatter mode="drop" still *wraps* negative indices, so route
    # unassigned/off-shard entries to an always-out-of-range sentinel
    blk = jnp.where(blk < 0, N, blk)
    return pool.at[blk, pos % bl].set(new[:, 0].astype(pool.dtype),
                                      mode="drop")


def append_kv(cache: jax.Array, new: jax.Array, pos: jax.Array) -> jax.Array:
    """Per-slot KV append: write ``new[b]`` at seq offset ``pos[b]``.

    ``cache`` is ``(B, S, *row)``, ``new`` ``(B, 1, *row)``, ``pos``
    ``(B,)`` — each batch entry lands at its own offset (continuous
    batching; oracle: :func:`repro.kernels.ref.decode_append_ref`).
    """
    def one(c, n, p):
        return jax.lax.dynamic_update_slice_in_dim(
            c, n.astype(c.dtype), p, axis=0)
    return jax.vmap(one)(cache, new, pos)


def _held_sum(held) -> Any:
    """Sum of the per-token held-pair counts over a scan step's layers
    (None entries are layers without an FFN)."""
    got = [h.sum(-1) for h in held if h is not None]
    return sum(got[1:], got[0])


def decode_step(arch: ArchConfig, params, cache, batch, cfg: RunCfg,
                stats: bool = False):
    """One-token decode across all layers. Returns (logits, new_cache);
    with ``stats`` a third item, ``{"moe_held_pairs": (B,) int32}`` —
    each row's token-expert pairs on held experts this step, summed
    over layers — under the held-expert layer (``{}`` otherwise)."""
    x, positions, _ = _embed_in(arch, params, batch, cfg)   # (B,1,d)
    B = x.shape[0]
    pos = jnp.asarray(cache["pos"], jnp.int32)
    if pos.ndim == 0:                   # legacy scalar: uniform offsets
        pos = jnp.full((B,), pos, jnp.int32)
    if "positions" not in batch:
        positions = pos[:, None]                            # (B, 1)
        if arch.mrope_sections is not None:
            positions = jnp.broadcast_to(positions, (3, B, 1))
    g = arch.moe_interleave if arch.is_moe and arch.moe_interleave > 1 else 1
    block_tbl = cache.get("block_tbl")        # paged residency marker
    pools = tuple(cache_rows(arch))
    counting = _counts_pairs(cfg)

    def attend(grp, h, w, kv):
        """Decode attention of one layer over its pools ``kv``; returns
        the layer's attention output and the appended pools."""
        if arch.is_mla:
            p = _mla_params(grp)
            with jax.named_scope("qkv"):
                q_nope, q_pe, row = attn_mod.mla_project(h, p, arch,
                                                         positions)
            with jax.named_scope("attention"):
                (c,) = kv
                if block_tbl is not None:
                    c = append_kv_paged(c, row, pos, block_tbl)
                    ctx = attn_mod.attention_decode_latent_paged(
                        q_nope, q_pe, c, block_tbl, p, arch,
                        cache_len=pos + 1)
                else:
                    c = append_kv(c, row, pos)
                    ctx = attn_mod.attention_decode_latent(
                        q_nope, q_pe, c, p, arch, cache_len=pos + 1)
            return ctx.reshape(B, 1, -1) @ p.wo, (c,)
        kc, vc = kv
        ap = _attn_params(grp)
        Hq = ap.wq.shape[-1] // arch.hd
        with jax.named_scope("qkv"):
            q, k, v = attn_mod.project_qkv(
                h, ap, Hq, ap.wk.shape[-1] // arch.hd, arch.hd,
                positions, arch.rope_theta, arch.mrope_sections,
                arch.norm_eps)
        with jax.named_scope("attention"):
            if cfg.decode_impl == "shard_map_flash" \
                    and cfg.mesh is not None:
                if block_tbl is not None:
                    from repro.dist.flash_decode import flash_decode_paged
                    ctx, kc, vc = flash_decode_paged(
                        q, k, v, kc, vc, block_tbl, pos, w, mesh=cfg.mesh,
                        data_axes=cfg.data_axes, model_axis=cfg.model_axis,
                        combine=cfg.combine_topology)
                else:
                    from repro.dist.flash_decode import flash_decode
                    ctx, kc, vc = flash_decode(
                        q, k, v, kc, vc, pos, w, mesh=cfg.mesh,
                        data_axes=cfg.data_axes, model_axis=cfg.model_axis,
                        combine=cfg.combine_topology)
            else:
                if not cfg.shard_heads:
                    pass
                elif cfg.kv_heads_sharded:
                    q = _hint(q, cfg, None, None, cfg.model_axis, None)
                    k = _hint(k, cfg, None, None, cfg.model_axis, None)
                    v = _hint(v, cfg, None, None, cfg.model_axis, None)
                else:
                    # match the head_dim-sharded cache: QK^T contracts the
                    # sharded dim -> psum of the score tensor, and the
                    # cache append stays local
                    q = _hint(q, cfg, None, None, "rep", cfg.model_axis)
                    k = _hint(k, cfg, None, None, "rep", cfg.model_axis)
                    v = _hint(v, cfg, None, None, "rep", cfg.model_axis)
                if block_tbl is not None:
                    kc = append_kv_paged(kc, k, pos, block_tbl)
                    vc = append_kv_paged(vc, v, pos, block_tbl)
                    ctx = attn_mod.attention_decode_paged(
                        q, kc, vc, block_tbl, cache_len=pos + 1, window=w)
                else:
                    kc = append_kv(kc, k, pos)
                    vc = append_kv(vc, v, pos)
                    ctx = attn_mod.attention_decode(q, kc, vc,
                                                    cache_len=pos + 1,
                                                    window=w)
        return ctx.reshape(B, 1, -1) @ ap.wo, (kc, vc)

    def layer(x, grp, w, kv, sc, cc):
        """One layer of decode; returns (x, new kv/sc/cc, held pairs)."""
        h = rms_norm(x, grp["pre_norm"], arch.norm_eps)
        out = 0.0
        n_paths = int(arch.has_attention) + int(arch.has_ssm)
        if arch.has_attention:
            y, kv = attend(grp, h, w, kv)
            out = out + y
        if arch.has_ssm:
            sp = SSMParams(**grp["ssm"])
            y, sc, cc = ssm_mod.ssm_decode_step(h, sp, _ssm_dims(arch, sp),
                                                sc, cc)
            out = out + y
        x = x + out / n_paths
        held = None
        if arch.family != "ssm" and ("mlp" in grp or "moe" in grp):
            with jax.named_scope("mlp"):
                x, _, held = _ffn_fwd(arch, cfg, grp, x)
        return x, kv, sc, cc, held

    # scan over layers with the FULL stacked cache in the carry: each
    # iteration slices its layer and updates it in place (dynamic-update-
    # slice on the unsharded layer dim), so the cache buffer is aliased
    # end-to-end (with donation) instead of double-buffered through ys.
    L = arch.n_layers
    Lg = L // g
    zeros = lambda: jnp.zeros((Lg, 1), jnp.float32)
    kv_full = tuple(cache[k] for k in pools) or (zeros(), zeros())
    sc_full = cache.get("ssm")
    cc_full = cache.get("conv")
    if g > 1:
        kv_full = tuple(f.reshape(Lg, g, *f.shape[1:]) for f in kv_full) \
            if pools else kv_full
        if sc_full is not None:
            sc_full = sc_full.reshape(Lg, g, *sc_full.shape[1:])
            cc_full = cc_full.reshape(Lg, g, *cc_full.shape[1:])
    sc_full = sc_full if sc_full is not None else zeros()
    cc_full = cc_full if cc_full is not None else zeros()

    def at(full, i):
        return jax.lax.dynamic_index_in_dim(full, i, axis=0, keepdims=False)

    def put(full, i, val):
        return jax.lax.dynamic_update_index_in_dim(full, val, i, axis=0)

    def body(carry, xs):
        x, i, kf, sf, cf, pairs = carry
        grp, w = xs
        kv = tuple(at(f, i) for f in kf)
        sc, cc = at(sf, i), at(cf, i)
        if g > 1:
            x, kv0, sc0, cc0, h0 = layer(x, grp["dense"], w[0],
                                         tuple(c[0] for c in kv),
                                         sc[0], cc[0])
            x, kv1, sc1, cc1, h1 = layer(x, grp["moe"], w[1],
                                         tuple(c[1] for c in kv),
                                         sc[1], cc[1])
            kv = tuple(jnp.stack([a, b]) for a, b in zip(kv0, kv1)) \
                if arch.has_attention else kv
            sc = jnp.stack([sc0, sc1]) if arch.has_ssm else sc
            cc = jnp.stack([cc0, cc1]) if arch.has_ssm else cc
            held = (h0, h1)
        else:
            x, kv, sc, cc, h = layer(x, grp, w, kv, sc, cc)
            held = (h,)
        if counting:
            pairs = pairs + _held_sum(held)
        return (x, i + 1, tuple(put(f, i, c) for f, c in zip(kf, kv)),
                put(sf, i, sc), put(cf, i, cc), pairs), None

    pairs = jnp.zeros((B,), jnp.int32) if counting else ()
    stacks = _stacks(arch, params)
    # the leading dense layers run before the loop, unrolled, and write
    # their cache rows back after it: a write into the carried cache
    # ahead of the loop would make XLA copy every pool whole
    lead = []
    for j in range(arch.first_dense_layers):
        grp, w = jax.tree.map(lambda a: a[j], stacks[0])
        x, kv, sc, cc, h = layer(x, grp, w, tuple(at(f, j) for f in kv_full),
                                 at(sc_full, j), at(cc_full, j))
        if counting:
            pairs = pairs + _held_sum((h,))
        lead.append((kv, sc, cc))
    carry = (x, jnp.asarray(len(lead), jnp.int32), kv_full, sc_full,
             cc_full, pairs)
    carry, _ = jax.lax.scan(body, carry, stacks[-1])
    x, _, new_kv, new_s, new_c, pairs = carry
    for j, (kv, sc, cc) in enumerate(lead):
        new_kv = tuple(put(f, j, c) for f, c in zip(new_kv, kv))
        new_s, new_c = put(new_s, j, sc), put(new_c, j, cc)
    with jax.named_scope("logits"):
        x = rms_norm(x, params["final_norm"], arch.norm_eps)
        logits = _logits(arch, params, x, cfg)
    new_cache = dict(cache)
    new_cache["pos"] = pos + 1
    for key, f in zip(pools, new_kv):
        new_cache[key] = f.reshape(L, *f.shape[2:]) if g > 1 else f
    if arch.has_ssm:
        new_cache["ssm"] = new_s.reshape(L, *new_s.shape[2:]) if g > 1 else new_s
        new_cache["conv"] = new_c.reshape(L, *new_c.shape[2:]) if g > 1 else new_c
    if not stats:
        return logits[:, 0], new_cache
    return logits[:, 0], new_cache, (
        {"moe_held_pairs": pairs} if counting else {})


def _concat_stacks(ys):
    """Per-stack scan outputs joined along the layer axis."""
    if len(ys) == 1:
        return ys[0]
    return jax.tree.map(lambda *a: jnp.concatenate(a, axis=0), *ys)


def prefill(arch: ArchConfig, params, batch, cfg: RunCfg, max_len: int = 0,
            stats: bool = False):
    """Process a prompt, build the session cache, return last-token logits.

    Implemented as the full-sequence forward plus cache extraction: each
    layer's scan step also emits the rows its cache gains (post-RoPE
    K/V, or MLA's latent row), padded to ``max_len``.  For SSM archs the
    final state comes from running the SSD scan.  With ``stats`` a
    third item as :func:`decode_step`'s, summed over the prompt.
    """
    x, positions, mask_pos = _embed_in(arch, params, batch, cfg)
    B, S = x.shape[:2]
    max_len = max_len or S
    g = arch.moe_interleave if arch.is_moe and arch.moe_interleave > 1 else 1
    counting = _counts_pairs(cfg)

    cache = init_cache(arch, B, max_len)

    def layer(x, grp, w):
        h = rms_norm(x, grp["pre_norm"], arch.norm_eps)
        out = 0.0
        n_paths = int(arch.has_attention) + int(arch.has_ssm)
        rows: Dict[str, Any] = {}
        states = ()
        if arch.has_attention:
            y, rows = _self_attention(arch, cfg, grp, h, positions,
                                      mask_pos, w)
            out = out + y
        if arch.has_ssm:
            sp = SSMParams(**grp["ssm"])
            y, fin_s, fin_c = _ssm_prefill(h, sp, arch, cfg)
            out = out + y
            states = (fin_s, fin_c)
        x = x + out / n_paths
        held = None
        if arch.family != "ssm" and ("mlp" in grp or "moe" in grp):
            x, _, held = _ffn_fwd(arch, cfg, grp, x)
        return x, rows, states, held

    def body(carry, xs):
        x, pairs = carry
        grp, w = xs
        if g > 1:
            x, r0, st0, h0 = layer(x, grp["dense"], w[0])
            x, r1, st1, h1 = layer(x, grp["moe"], w[1])
            ys = _stack_cache(arch, (r0, r1), (st0, st1), max_len, S)
            held = (h0, h1)
        else:
            x, r, st, h = layer(x, grp, w)
            ys = _stack_cache(arch, (r,), (st,), max_len, S)
            held = (h,)
        if counting:
            pairs = pairs + _held_sum(held)
        return (x, pairs), ys

    carry = (x, jnp.zeros((B,), jnp.int32) if counting else ())
    ys = []
    for xs in _stacks(arch, params):
        carry, y = jax.lax.scan(body, carry, xs)
        ys.append(y)
    ys = _concat_stacks(ys)
    x, pairs = carry
    x = rms_norm(x, params["final_norm"], arch.norm_eps)
    logits = _logits(arch, params, x[:, -1:], cfg)

    # unpack stacked cache entries
    L = arch.n_layers
    for key, row in cache_rows(arch).items():
        cache[key] = ys[key].reshape(L, B, max_len, *ys[key].shape[-len(row):])
    if arch.has_ssm:
        cache["ssm"] = ys["ssm"].reshape(L, *ys["ssm"].shape[-4:])
        cache["conv"] = ys["conv"].reshape(L, *ys["conv"].shape[-3:])
    cache["pos"] = jnp.full((B,), S, jnp.int32)
    if not stats:
        return logits[:, 0], cache
    return logits[:, 0], cache, (
        {"moe_held_pairs": pairs} if counting else {})


def prefill_tail(arch: ArchConfig, params, batch, cfg: RunCfg,
                 prefix: Dict[str, jax.Array], stats: bool = False):
    """Prefill only the unmatched *tail* of a prompt whose leading rows
    already sit in resident pool blocks (cross-request prefix reuse).

    ``batch["tokens"]`` is ``(B, T)`` tail tokens; ``prefix`` maps each
    pool of :func:`cache_rows` to ``(L, B, M, *row)`` — the matched
    prefix rows gathered from the pool (post-RoPE at absolute positions
    ``[0, M)``, exactly as the donor's prefill wrote them; MLA expands
    its latent rows to per-head keys and values here, as prefill does).
    The tail is embedded at absolute positions ``M + [0, T)`` and each
    layer attends over prefix-plus-tail keys through
    :func:`repro.models.attention.attention_tail`, whose op structure
    matches the full-prefill attention bit-for-bit on the tail
    positions — the token-identity contract aliased admission leans on.

    Returns ``(last-token logits (B, V), tail)`` with ``tail`` mapping
    each pool to ``(L, B, T, *row)`` rows for the caller to scatter into
    its freshly allocated blocks (and with ``stats`` a third item as
    :func:`decode_step`'s, over the tail).  Attention-only
    architectures: an SSM path's state at position M depends on every
    earlier token, so a hybrid cannot skip the prefix compute (the
    engine runs those through the full prefill and aliases blocks
    without skipping).
    """
    if arch.has_ssm:
        raise ValueError(
            f"prefill_tail cannot skip prefix compute for {arch.name}: "
            "SSM state at the split point depends on the whole prefix")
    tokens = batch["tokens"]
    B, T = tokens.shape
    M = next(iter(prefix.values())).shape[2]
    x = jnp.take(params["embed"], tokens, axis=0)
    q_pos = jnp.broadcast_to(M + jnp.arange(T, dtype=jnp.int32), (B, T))
    k_pos = jnp.broadcast_to(jnp.arange(M + T, dtype=jnp.int32), (B, M + T))
    positions = q_pos
    if arch.mrope_sections is not None:
        positions = jnp.broadcast_to(positions, (3, B, T))
    g = arch.moe_interleave if arch.is_moe and arch.moe_interleave > 1 else 1
    counting = _counts_pairs(cfg)

    def layer(x, grp, w, pre):
        h = rms_norm(x, grp["pre_norm"], arch.norm_eps)
        y, rows = _self_attention(arch, cfg, grp, h, positions, None, w,
                                  prefix=pre, q_pos=q_pos, k_pos=k_pos)
        x = x + y
        held = None
        if "mlp" in grp or "moe" in grp:
            x, _, held = _ffn_fwd(arch, cfg, grp, x)
        return x, rows, held

    def body(carry, xs):
        x, pairs = carry
        grp, w, pre = xs
        if g > 1:
            x, r0, h0 = layer(x, grp["dense"], w[0],
                              {k: v[0] for k, v in pre.items()})
            x, r1, h1 = layer(x, grp["moe"], w[1],
                              {k: v[1] for k, v in pre.items()})
            rows = {k: jnp.stack([r0[k], r1[k]]) for k in r0}
            held = (h0, h1)
        else:
            x, rows, h = layer(x, grp, w, pre)
            held = (h,)
        if counting:
            pairs = pairs + _held_sum(held)
        return (x, pairs), rows

    carry = (x, jnp.zeros((B,), jnp.int32) if counting else ())
    ys = []
    at = 0
    for grp, w in _stacks(arch, params):
        n = w.shape[0] * g
        pre = {k: v[at:at + n] for k, v in prefix.items()}
        if g > 1:
            pre = {k: v.reshape(n // g, g, *v.shape[1:])
                   for k, v in pre.items()}
        carry, y = jax.lax.scan(body, carry, (grp, w, pre))
        ys.append(y)
        at += n
    ys = _concat_stacks(ys)
    x, pairs = carry
    x = rms_norm(x, params["final_norm"], arch.norm_eps)
    logits = _logits(arch, params, x[:, -1:], cfg)
    L = arch.n_layers
    tail = {k: v.reshape(L, B, T, *prefix[k].shape[3:])
            for k, v in ys.items()}
    if not stats:
        return logits[:, 0], tail
    return logits[:, 0], tail, (
        {"moe_held_pairs": pairs} if counting else {})


def prefill_chunked(arch: ArchConfig, params, tokens, chunk: int,
                    cfg: RunCfg, kv_heads: int = 0,
                    prefix: Optional[Dict[str, Any]] = None,
                    on_chunk=None, tail_fn=None):
    """Block-native chunked prefill of ONE prompt: no dense ``(B, plen)``
    intermediate ever exists.

    ``tokens`` is ``(T,)`` — the part of the feed *after* any prefix
    already in hand; ``prefix`` maps each pool of :func:`cache_rows` to
    ``(L, M, *row)`` rows covering the first M tokens (``None`` for a
    fresh prompt).  The tail is processed in ``chunk``-sized slices,
    each one a :func:`prefill_tail` call chained on the rows
    accumulated so far — every slice comes out pool-block-shaped, ready
    to scatter straight into paged blocks.  Because ``attention_tail``
    mirrors the full-prefill ``attention_chunked`` op-for-op, the
    chained chunks reproduce the dense prefill's hidden states exactly:
    same cache rows, same last-token logits (pinned by ``test_disagg``).

    ``on_chunk(block_idx, rows)`` fires after each slice with ``{pool:
    (L, t, *row)}`` (``block_idx`` counts from the start of the *feed*,
    prefix included) — the disagg worker streams these to the decode
    engine and heartbeats between them.  ``tail_fn`` lets a long-lived
    caller supply a pre-jitted ``prefill_tail`` closure so the
    per-shape compile cache survives across prompts.

    Returns ``(last-token logits (V,), chunks)``: one rows dict per
    slice.  Attention-only archs (same restriction as
    ``prefill_tail``).
    """
    if arch.has_ssm:
        raise ValueError(
            f"prefill_chunked needs pure-attention KV for {arch.name}: "
            "SSM state is sequential across the whole prompt")
    if chunk < 1:
        raise ValueError(f"chunk must be >= 1, got {chunk}")
    tokens = jnp.asarray(tokens, jnp.int32)
    (T,) = tokens.shape
    if T < 1:
        raise ValueError("prefill_chunked needs at least one tail token")
    L = arch.n_layers
    if prefix is None:
        pre = {k: jnp.zeros((L, 1, 0, *row), jnp.bfloat16)
               for k, row in cache_rows(arch, kv_heads).items()}
    else:
        pre = {k: jnp.asarray(v)[:, None] for k, v in prefix.items()}
    M = next(iter(pre.values())).shape[2]
    if M % chunk:
        raise ValueError(f"prefix length {M} not block-aligned to {chunk}")
    if tail_fn is None:
        tail_fn = lambda p, b, pr: prefill_tail(arch, p, b, cfg, pr)
    chunks = []
    logits = None
    for i in range(0, T, chunk):
        tok = tokens[None, i:i + chunk]                      # (1, t)
        logits, tail = tail_fn(params, {"tokens": tok}, pre)
        chunks.append({k: v[:, 0] for k, v in tail.items()})  # (L, t, ...)
        if on_chunk is not None:
            on_chunk((M + i) // chunk, chunks[-1])
        pre = {k: jnp.concatenate([pre[k], tail[k]], axis=2) for k in pre}
    return logits[0], chunks


def _ssm_prefill(h, sp, arch, cfg):
    """SSD forward that also returns the final (ssm, conv) states."""
    dims = _ssm_dims(arch, sp)
    B, S, d = h.shape
    di, G, N = dims.d_inner, dims.n_groups, dims.state
    zxbcdt = h @ sp.in_proj
    z, xbc_raw, dt = jnp.split(zxbcdt, [di, 2 * di + 2 * G * N], axis=-1)
    xbc = ssm_mod.causal_conv(xbc_raw, sp.conv_w, sp.conv_b)
    xbc = jax.nn.silu(xbc.astype(jnp.float32))
    xs, Bm, Cm = jnp.split(xbc, [di, di + G * N], axis=-1)
    xs = xs.reshape(B, S, dims.n_heads, dims.head_dim)
    Bm = Bm.reshape(B, S, G, N)
    Cm = Cm.reshape(B, S, G, N)
    dtv = jax.nn.softplus(dt.astype(jnp.float32) + sp.dt_bias)
    A = -jnp.exp(sp.A_log)
    y, final = ssm_mod.ssd_chunked(xs, dtv, A, Bm, Cm, chunk=cfg.ssd_chunk)
    y = y + xs * sp.D[None, None, :, None]
    y = y.reshape(B, S, di)
    y = rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype), sp.norm)
    y = (y @ sp.out_proj).astype(h.dtype)
    k = dims.conv_k
    conv_fin = jnp.pad(xbc_raw, ((0, 0), (k - 1, 0), (0, 0)))[:, -k:, :] \
        .astype(jnp.bfloat16)
    return y, final, conv_fin


def _stack_cache(arch, rows, states, max_len, S):
    """The per-scan-step cache ys: each pool's rows padded to
    ``max_len`` (``rows`` holds one dict per layer of the step), and the
    SSM states."""
    out = {}
    for key in (rows[0] if arch.has_attention else ()):
        r = jnp.stack([
            jnp.pad(x[key], ((0, 0), (0, max_len - S))
                    + ((0, 0),) * (x[key].ndim - 2)) for x in rows])
        out[key] = r[0] if len(rows) == 1 else r
    if arch.has_ssm:
        ss = jnp.stack([s[0] for s in states])
        cs = jnp.stack([s[1] for s in states])
        if len(states) == 1:
            ss, cs = ss[0], cs[0]
        out["ssm"], out["conv"] = ss, cs
    return out
