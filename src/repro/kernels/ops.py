"""Jit'd kernel wrappers configured by the plan's BlockPlans.

Each op runs either the Pallas kernel or the pure-jnp oracle in
:mod:`repro.kernels.ref`.  ``mode`` picks: ``"on"`` the kernel, ``"off"``
the oracle, ``"auto"`` the kernel iff the default backend is a TPU (the
oracle is the only path elsewhere).  The kernel runs compiled for the
TPU; interpret mode (CPU validation) runs only when ``interpret=True`` is
passed, and asking for the kernel on another backend without it raises.
Tiles come from the plan's BlockPlans — kernel code never picks its own
(paper §4: the template is parameterized by the compiler, the datapath
just runs).

The model path does not call these wrappers: prefill and decode
attention are XLA-level (:mod:`repro.models.attention`,
:mod:`repro.dist.flash_decode`), and the plan's ``use_pallas`` is only
recorded.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.plan import BlockPlan, MemoryPlan
from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention as _decode_pallas
from repro.kernels.flash_attention import flash_attention as _flash_pallas
from repro.kernels.ssd_scan import ssd_scan as _ssd_pallas
from repro.kernels.tiled_matmul import tiled_matmul as _mm_pallas


def _use_pallas(mode: str, interpret: bool) -> bool:
    if interpret:
        return True
    if mode == "off":
        return False
    if mode not in ("on", "auto"):
        raise ValueError(f"mode must be 'on', 'off' or 'auto', got {mode!r}")
    on_tpu = jax.default_backend() == "tpu"
    if mode == "on" and not on_tpu:
        raise RuntimeError(
            f"the Pallas kernel needs a TPU backend, this one is "
            f"{jax.default_backend()!r}; pass interpret=True to run it in "
            "interpret mode")
    return on_tpu


def _blocks(plan: Optional[MemoryPlan], kernel: str) -> Optional[BlockPlan]:
    if plan is None:
        return None
    return plan.partitions.get(kernel)


def flash_attention(q, k, v, *, causal=True, window=0,
                    plan: Optional[MemoryPlan] = None, mode="auto",
                    interpret=False):
    bp = _blocks(plan, "flash_attention")
    if _use_pallas(mode, interpret):
        return _flash_pallas(
            q, k, v, causal=causal, window=window,
            block_q=bp.blocks["block_q"] if bp else 512,
            block_kv=bp.blocks["block_kv"] if bp else 1024,
            interpret=interpret)
    return ref.flash_attention_ref(q, k, v, causal=causal, window=window)


def decode_attention(q, k, v, *, cache_len, window=0,
                     plan: Optional[MemoryPlan] = None, mode="auto",
                     interpret=False):
    bp = _blocks(plan, "decode_attention")
    if _use_pallas(mode, interpret):
        return _decode_pallas(
            q, k, v, cache_len=cache_len, window=window,
            block_kv=bp.blocks["block_kv"] if bp else 2048,
            interpret=interpret)
    return ref.decode_attention_ref(q, k, v, cache_len=cache_len,
                                    window=window)


def ssd_scan(x, dt, A, Bm, Cm, *, plan: Optional[MemoryPlan] = None,
             mode="auto", interpret=False):
    bp = _blocks(plan, "ssd_scan")
    if _use_pallas(mode, interpret):
        y = _ssd_pallas(
            x, dt, A, Bm, Cm,
            chunk=bp.blocks["chunk"] if bp else 256,
            interpret=interpret)
        return y
    y, _ = ref.ssd_scan_ref(x, dt, A, Bm, Cm)
    return y


def matmul(a, b, *, plan: Optional[MemoryPlan] = None, mode="auto",
           interpret=False):
    bp = _blocks(plan, "tiled_matmul")
    if _use_pallas(mode, interpret):
        return _mm_pallas(
            a, b,
            bm=bp.blocks["bm"] if bp else 512,
            bk=bp.blocks["bk"] if bp else 512,
            bn=bp.blocks["bn"] if bp else 512,
            interpret=interpret)
    return ref.tiled_matmul_ref(a, b)
