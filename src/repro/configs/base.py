"""Architecture + shape configuration system.

Every assigned architecture is a frozen :class:`ArchConfig`; shapes are
:class:`ShapeConfig`.  ``registry()`` exposes them to the CLI
(``--arch <id> --shape <id>``).  Reduced configs for CPU smoke tests come
from :meth:`ArchConfig.reduced`.
"""

from __future__ import annotations

import dataclasses
import importlib
import math
from typing import Dict, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                  # dense | moe | ssm | hybrid | encoder
    modality: str = "text"       # text | audio | vlm
    n_layers: int = 0
    d_model: int = 0
    n_heads: int = 0
    n_kv_heads: int = 0
    d_ff: int = 0
    vocab_size: int = 0
    head_dim: int = 0            # 0 -> d_model // n_heads

    # attention details
    causal: bool = True
    qk_norm: bool = False
    rope_theta: float = 1e4
    mrope_sections: Optional[Tuple[int, int, int]] = None  # qwen2-vl
    window: int = 0              # 0 = full attention; >0 = sliding window
    global_every: int = 0        # hymba: every k-th layer is global attn
    norm_eps: float = 1e-6
    tie_embeddings: bool = False
    gated_mlp: bool = True       # SwiGLU (3 mats) vs squared-ReLU (2 mats)

    # MoE
    n_experts: int = 0
    experts_per_token: int = 0
    moe_d_ff: int = 0            # expert hidden dim (0 -> d_ff)
    moe_interleave: int = 1      # MoE every k-th layer (llama4: 2)
    n_shared_experts: int = 0
    capacity_factor: float = 1.25
    first_dense_layers: int = 0  # deepseek: leading dense layers (d_ff)
    # routed experts whose weights this configuration holds, numbered
    # 0..n-1 of the router's n_experts (0 -> all): one chip's share
    # under expert parallelism; the rest of the routed sum is left out
    n_routed_experts: int = 0

    # latent attention (MLA, deepseek-v2): kv_lora_rank > 0 caches one
    # row of kv_lora_rank + qk_rope_head_dim values a token and layer
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # YaRN rope scaling (arXiv:2309.00071, as deepseek-v2 reads it);
    # yarn_factor 0 is plain rope
    yarn_factor: float = 0.0
    yarn_original_len: int = 0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 0.0

    # SSM (mamba2)
    ssm_state: int = 0
    ssm_conv: int = 4
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_n_groups: int = 1

    dtype: str = "bfloat16"
    source: str = ""             # provenance tag from the assignment

    # ------------------------------------------------------------------
    @property
    def hd(self) -> int:
        return self.head_dim or (self.d_model // max(self.n_heads, 1))

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim if self.ssm_state else 0

    @property
    def has_attention(self) -> bool:
        return self.n_heads > 0

    @property
    def has_ssm(self) -> bool:
        return self.ssm_state > 0

    @property
    def is_encoder(self) -> bool:
        return self.family == "encoder"

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    @property
    def experts_held(self) -> int:
        """Routed experts whose weights are held (experts 0..n-1)."""
        return self.n_routed_experts or self.n_experts

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def kv_row_width(self) -> int:
        """Cached values a token and layer: the latent row under MLA."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    def cache_bytes_per_token(self, dtype_bytes: int = 2) -> int:
        """Bytes the attention cache holds for one token, every layer:
        one latent row under MLA, else K and V of every kv head."""
        if not self.has_attention:
            return 0
        row = (self.kv_row_width if self.is_mla
               else 2 * self.n_kv_heads * self.hd)
        return self.n_layers * row * dtype_bytes

    def attn_param_count(self) -> int:
        """Attention weights of one layer."""
        d, H = self.d_model, self.n_heads
        if self.is_mla:
            nope, rope = self.qk_nope_head_dim, self.qk_rope_head_dim
            r, dv = self.kv_lora_rank, self.v_head_dim
            return (d * H * (nope + rope) + d * (r + rope) + r
                    + r * H * (nope + dv) + H * dv * d)
        return d * H * self.hd * 2 + 2 * d * self.n_kv_heads * self.hd

    @property
    def sub_quadratic(self) -> bool:
        """Can this arch handle 500k-token context (decode) sanely?"""
        if self.family in ("ssm", "hybrid"):
            return True
        return self.window > 0

    @property
    def n_moe_layers(self) -> int:
        if not self.is_moe:
            return 0
        return (self.n_layers - self.first_dense_layers) // self.moe_interleave

    # --- parameter counts (analytical; cross-checked in tests) ---------
    def param_count(self) -> int:
        d, L = self.d_model, self.n_layers
        n = 0
        # embeddings (+ untied head)
        n += self.vocab_size * d * (1 if self.tie_embeddings else 2)
        per_layer = 0
        if self.has_attention:
            per_layer += self.attn_param_count()
        if self.has_ssm:
            di, g, s = self.d_inner, self.ssm_n_groups, self.ssm_state
            in_proj = d * (2 * di + 2 * g * s + self.ssm_heads)
            conv = (di + 2 * g * s) * self.ssm_conv
            out = di * d
            per_layer += in_proj + conv + out + 2 * self.ssm_heads  # A,D
        n += per_layer * L
        # FFN: dense layers + MoE layers
        if self.is_moe:
            moe_ff = self.moe_d_ff or self.d_ff
            n_moe_layers = self.n_moe_layers
            n_dense_layers = L - n_moe_layers
            n += n_moe_layers * self.experts_held * 3 * d * moe_ff
            n += n_moe_layers * self.n_shared_experts * 3 * d * moe_ff
            n += n_moe_layers * d * self.n_experts          # router
            n += n_dense_layers * 3 * d * self.d_ff
        elif self.d_ff:
            # SwiGLU (gate, up, down) vs plain 2-matrix FFN (hubert, minitron)
            mult = 3 if (self.gated_mlp and self.family != "encoder") else 2
            n += L * mult * d * self.d_ff
        # norms
        n += L * 2 * d + d
        return n

    def active_param_count(self) -> int:
        """Per-token active params (MoE: only routed experts count)."""
        if not self.is_moe:
            return self.param_count()
        full = self.param_count()
        moe_ff = self.moe_d_ff or self.d_ff
        n_moe_layers = self.n_moe_layers
        all_experts = (n_moe_layers * self.experts_held * 3 * self.d_model
                       * moe_ff)
        active = n_moe_layers * self.experts_per_token * 3 * self.d_model * moe_ff
        return full - all_experts + active

    # ------------------------------------------------------------------
    def reduced(self) -> "ArchConfig":
        """Tiny same-family config for CPU smoke tests."""
        r = lambda v, m: min(v, m) if v else v
        mrope = None
        if self.mrope_sections is not None:
            half = 32 // 2          # reduced head_dim is 32
            s = half * 3 // 8
            mrope = (half - 2 * s, s, s)
        mla = {}
        if self.is_mla:
            mla = dict(kv_lora_rank=32, qk_nope_head_dim=16,
                       qk_rope_head_dim=8, v_head_dim=16)
        return dataclasses.replace(
            self,
            **mla,
            n_layers=min(self.n_layers, 2 * max(self.moe_interleave, 1)
                         + self.first_dense_layers),
            d_model=128,
            n_heads=r(self.n_heads, 4),
            n_kv_heads=r(self.n_kv_heads, 2),
            head_dim=32 if self.n_heads else 0,
            d_ff=r(self.d_ff, 256) if self.d_ff else 0,
            moe_d_ff=r(self.moe_d_ff, 128) if self.moe_d_ff else 0,
            vocab_size=min(self.vocab_size, 512),
            n_experts=r(self.n_experts, 8),
            n_routed_experts=r(self.n_routed_experts, 8),
            experts_per_token=r(self.experts_per_token, 2),
            ssm_state=r(self.ssm_state, 16),
            ssm_head_dim=32 if self.ssm_state else 64,
            window=min(self.window, 64) if self.window else 0,
            mrope_sections=mrope,
        )


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    kind: str          # "train" | "prefill" | "decode"
    seq_len: int
    global_batch: int

    @property
    def tokens(self) -> int:
        if self.kind == "decode":
            return self.global_batch          # one new token per sequence
        return self.seq_len * self.global_batch


SHAPES: Dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", "train", 4096, 256),
    "prefill_32k": ShapeConfig("prefill_32k", "prefill", 32768, 32),
    "decode_32k": ShapeConfig("decode_32k", "decode", 32768, 128),
    "long_500k": ShapeConfig("long_500k", "decode", 524288, 1),
}


def applicable(arch: ArchConfig, shape: ShapeConfig) -> Tuple[bool, str]:
    """Which (arch × shape) cells are runnable (DESIGN.md §5)."""
    if arch.is_encoder and shape.kind == "decode":
        return False, "encoder-only arch has no decode step"
    if shape.name == "long_500k" and not arch.sub_quadratic:
        return False, "pure full-attention arch; 500k KV decode excluded per spec"
    return True, ""


_ARCH_MODULES = {
    "hubert-xlarge": "hubert_xlarge",
    "qwen2-vl-72b": "qwen2_vl_72b",
    "mamba2-2.7b": "mamba2_2p7b",
    "granite-moe-1b-a400m": "granite_moe_1b_a400m",
    "llama4-maverick-400b-a17b": "llama4_maverick_400b_a17b",
    "qwen3-8b": "qwen3_8b",
    "deepseek-7b": "deepseek_7b",
    "deepseek-coder-33b": "deepseek_coder_33b",
    "minitron-8b": "minitron_8b",
    "hymba-1.5b": "hymba_1p5b",
    "deepseek-v2-lite": "deepseek_v2_lite",
}


def get_arch(name: str) -> ArchConfig:
    if name not in _ARCH_MODULES:
        raise KeyError(f"unknown arch {name!r}; have {sorted(_ARCH_MODULES)}")
    mod = importlib.import_module(f"repro.configs.{_ARCH_MODULES[name]}")
    return mod.CONFIG


def run_arch(name: str, *, reduced: bool = False, layers: int = 0
             ) -> Tuple[ArchConfig, Dict[str, Tuple[object, object]]]:
    """The config a launcher runs, and every key changed from the
    published one as ``{key: (published, run)}``.

    The published widths are the default.  ``layers`` cuts depth only
    (the chip's share of a pipeline; every width stays as published);
    ``reduced`` swaps in the tiny CPU config of :meth:`ArchConfig.reduced`.
    """
    src = get_arch(name)
    arch = src.reduced() if reduced else src
    if layers:
        g = max(arch.moe_interleave, 1) if arch.is_moe else 1
        if not 0 < layers <= arch.n_layers or \
                (layers - arch.first_dense_layers) % g:
            raise ValueError(
                f"layers={layers} must be in [1, {arch.n_layers}] and a "
                f"multiple of the layer period {g} for {name}")
        arch = dataclasses.replace(arch, n_layers=layers)
    changed = {f.name: (getattr(src, f.name), getattr(arch, f.name))
               for f in dataclasses.fields(src)
               if getattr(src, f.name) != getattr(arch, f.name)}
    return arch, changed


def get_shape(name: str) -> ShapeConfig:
    if name not in SHAPES:
        raise KeyError(f"unknown shape {name!r}; have {sorted(SHAPES)}")
    return SHAPES[name]


def all_archs() -> Tuple[str, ...]:
    return tuple(_ARCH_MODULES)


def all_cells() -> Tuple[Tuple[str, str, bool, str], ...]:
    """Every (arch, shape, runnable, reason) cell — 44 total."""
    out = []
    for a in all_archs():
        cfg = get_arch(a)
        for s in SHAPES:
            ok, why = applicable(cfg, SHAPES[s])
            out.append((a, s, ok, why))
    return tuple(out)
