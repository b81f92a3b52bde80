"""Mixture-of-Experts layer: capacity-based (GShard-style) routing.

Two execution paths, chosen by the communication pass:

* ``gshard_einsum`` — dispatch/combine one-hot einsums under plain pjit;
  XLA inserts the token↔expert all-to-alls from the shardings.  This is
  the baseline (paper-faithful "the compiler sees the IR and places the
  transfers").
* ``shard_map_alltoall`` — explicit ``jax.lax.all_to_all`` over the
  ``model`` axis inside ``shard_map``: the hand-scheduled collective
  pattern used in the beyond-paper perf iterations.

Both produce identical math (tested for equivalence); tokens over
capacity are dropped (capacity_factor 1.25 by default) and the router
adds the standard load-balancing auxiliary loss.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


class MoEParams(NamedTuple):
    router: jax.Array          # (d, E) fp32
    wi: jax.Array              # (E, d, 2*ff)  gate||up
    wo: jax.Array              # (E, ff, d)
    shared_wi: Optional[jax.Array] = None   # (d, 2*ff*n_shared)
    shared_wo: Optional[jax.Array] = None   # (ff*n_shared, d)


def _capacity(tokens_per_group: int, n_experts: int, top_k: int,
              capacity_factor: float) -> int:
    c = int(tokens_per_group * top_k * capacity_factor / n_experts)
    return max(4, -(-c // 4) * 4)          # multiple of 4, at least 4


def route(
    x: jax.Array,                # (G, T, d)  G groups of T tokens
    router_w: jax.Array,         # (d, E)
    top_k: int,
    capacity: int,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Top-k routing with per-group capacity.

    Returns (dispatch (G,T,E,C) bf16, combine (G,T,E,C) f32, aux_loss).
    """
    G, T, d = x.shape
    E = router_w.shape[-1]
    logits = (x.astype(jnp.float32) @ router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                  # (G,T,E)

    # standard load-balance aux loss (Switch): E * mean(f_e * p_e)
    top1 = jnp.argmax(probs, axis=-1)
    frac_tokens = jnp.mean(jax.nn.one_hot(top1, E, dtype=jnp.float32), axis=1)
    frac_probs = jnp.mean(probs, axis=1)
    aux = E * jnp.mean(jnp.sum(frac_tokens * frac_probs, axis=-1))

    remaining = probs
    dispatch = jnp.zeros((G, T, E, capacity), dtype=x.dtype)
    combine = jnp.zeros((G, T, E, capacity), dtype=jnp.float32)
    # fill counts per expert as we take top-k slots sequentially
    fill = jnp.zeros((G, E), dtype=jnp.int32)
    for _ in range(top_k):
        idx = jnp.argmax(remaining, axis=-1)                 # (G,T)
        gate = jnp.take_along_axis(remaining, idx[..., None], -1)[..., 0]
        remaining = remaining * (1.0 - jax.nn.one_hot(idx, E, dtype=jnp.float32))
        oh = jax.nn.one_hot(idx, E, dtype=jnp.int32)          # (G,T,E)
        pos = fill[:, None, :] + jnp.cumsum(oh, axis=1) - oh  # slot per token
        fill = fill + jnp.sum(oh, axis=1)
        within = (pos < capacity) & (oh > 0)                  # (G,T,E)
        slot = jnp.where(within, pos, 0)
        one_hot_slot = jax.nn.one_hot(slot, capacity, dtype=jnp.float32) \
            * within[..., None]
        dispatch = dispatch + one_hot_slot.astype(x.dtype)
        combine = combine + one_hot_slot * gate[..., None, None]
    return dispatch, combine, aux


def moe_dense_einsum(
    x: jax.Array,                # (B, S, d)
    p: MoEParams,
    *,
    top_k: int,
    capacity_factor: float = 0.0,   # unused; signature parity
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Dense-execution MoE: run every held expert on every token, combine
    with the top-k router weights.

    For small-expert/high-top-k configs (granite: 8-of-32, ff=512) the
    GShard dispatch/combine one-hot matmuls cost MORE FLOPs than simply
    computing all experts — and this path has no capacity drops, no
    (T,E,C) tensors, and no all-to-all.  The communication pass picks it
    when 6·E·ff <= 6·k·ff + 4·k·cf·(E·C/T)·... (see _moe_impl decision).

    ``wi``/``wo`` may hold fewer experts than the router scores: experts
    ``[0, E_h)`` of ``E``, one chip's share under expert parallelism.
    The router still scores all ``E`` and each token keeps its greedy
    top-k, unrenormalized; the result is this share's part of the routed
    sum (plus the shared experts, which every share runs).  On a v5e
    this beat sorting the pairs by held expert into grouped products
    (``lax.ragged_dot``) at 32 to 10240 tokens, though it does
    ``E_h / (k E_h / E)`` times the routed FLOPs.

    Returns ``(y, aux, held)``: ``held`` (B, S) int32 counts each
    token's pairs on held experts.
    """
    B, S, d = x.shape
    E = p.router.shape[-1]
    Eh = p.wi.shape[0]
    with jax.named_scope("router"):
        logits = x.astype(jnp.float32) @ p.router.astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)              # (B,S,E)
        top1 = jnp.argmax(probs, axis=-1)
        frac_tokens = jnp.mean(jax.nn.one_hot(top1, E, dtype=jnp.float32),
                               axis=(0, 1))
        frac_probs = jnp.mean(probs, axis=(0, 1))
        aux = E * jnp.sum(frac_tokens * frac_probs)

        # top-k gate weights of the held experts, zero elsewhere
        thresh = jax.lax.top_k(probs, top_k)[0][..., -1:]
        picked = (probs >= thresh)[..., :Eh]                 # (B,S,Eh)
        gates = jnp.where(picked, probs[..., :Eh], 0.0)

    with jax.named_scope("held"):
        h = jnp.einsum("bsd,edf->bsef", x, p.wi)             # (B,S,Eh,2ff)
        gate, up = jnp.split(h, 2, axis=-1)
        h = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
        out = jnp.einsum("bsef,efd->bsed", h, p.wo)          # (B,S,Eh,d)
        y = jnp.einsum("bsed,bse->bsd", out.astype(jnp.float32), gates)
        y = y.astype(x.dtype)
    if p.shared_wi is not None:
        with jax.named_scope("shared"):
            hs = x @ p.shared_wi
            gs, us = jnp.split(hs, 2, axis=-1)
            y = y + (jax.nn.silu(gs.astype(jnp.float32)).astype(x.dtype)
                     * us) @ p.shared_wo
    held = jnp.sum(picked, axis=-1, dtype=jnp.int32)
    return y, aux, held


def moe_gshard_einsum(
    x: jax.Array,                # (B, S, d)
    p: MoEParams,
    *,
    top_k: int,
    capacity_factor: float,
) -> Tuple[jax.Array, jax.Array]:
    """Einsum dispatch path (pjit shards: B->data, E->model)."""
    B, S, d = x.shape
    E = p.router.shape[-1]
    C = _capacity(S, E, top_k, capacity_factor)
    dispatch, combine, aux = route(x, p.router, top_k, C)     # (B,S,E,C)
    # token -> expert slots (XLA: all-to-all from B-shard to E-shard)
    expert_in = jnp.einsum("bsec,bsd->ebcd", dispatch, x)
    h = jnp.einsum("ebcd,edf->ebcf", expert_in, p.wi)
    gate, up = jnp.split(h, 2, axis=-1)
    h = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
    expert_out = jnp.einsum("ebcf,efd->ebcd", h, p.wo)
    y = jnp.einsum("ebcd,bsec->bsd", expert_out.astype(jnp.float32), combine)
    y = y.astype(x.dtype)
    if p.shared_wi is not None:
        hs = x @ p.shared_wi
        gs, us = jnp.split(hs, 2, axis=-1)
        y = y + (jax.nn.silu(gs.astype(jnp.float32)).astype(x.dtype) * us) @ p.shared_wo
    return y, aux


def moe_shard_map(
    x: jax.Array,                # (B, S, d) — sharded (data, model, None)
    p: MoEParams,
    *,
    top_k: int,
    capacity_factor: float,
    mesh: jax.sharding.Mesh,
    data_axes: Tuple[str, ...] = ("data",),
    model_axis: str = "model",
) -> Tuple[jax.Array, jax.Array]:
    """Explicit expert-parallel path: tokens sharded over (data×model),
    experts sharded over ``model``; two ragged-free all_to_alls move
    capacity slots between the layouts.  Beyond-paper optimization: the
    dispatch tensor never exists at global size and the collective is a
    single fused all-to-all instead of XLA's inferred pair.
    """
    E = p.router.shape[-1]
    tp = mesh.shape[model_axis]
    E_local = E // tp
    B, S, _ = x.shape
    # decode steps have S=1: keep tokens replicated over the model axis then
    seq_spec = model_axis if S % max(tp, 1) == 0 and S >= tp else None
    all_axes = tuple(data_axes) + (model_axis,)

    def local(x_l, router, wi, wo, *shared):
        # x_l: (B_l, S_l, d) — tokens on this chip
        Bl, Sl, d = x_l.shape
        toks = x_l.reshape(1, Bl * Sl, d)
        C = _capacity(Bl * Sl, E, top_k, capacity_factor)
        dispatch, combine, aux = route(toks, router, top_k, C)
        # (1,T,E,C) -> local contribution to every expert's slots
        send = jnp.einsum("gtec,gtd->ecd", dispatch, toks)      # (E,C,d)
        send = send.reshape(tp, E_local, C, d)
        # exchange: each peer receives its experts' slots from everyone
        recv = jax.lax.all_to_all(send, model_axis, split_axis=0,
                                  concat_axis=0, tiled=False)   # (tp,E_l,C,d)
        expert_in = recv.transpose(1, 0, 2, 3).reshape(E_local, tp * C, d)
        h = jnp.einsum("ecd,edf->ecf", expert_in, wi)
        gate, up = jnp.split(h, 2, axis=-1)
        h = jax.nn.silu(gate.astype(jnp.float32)).astype(x_l.dtype) * up
        out = jnp.einsum("ecf,efd->ecd", h, wo)                 # (E_l,tp*C,d)
        out = out.reshape(E_local, tp, C, d).transpose(1, 0, 2, 3)
        back = jax.lax.all_to_all(out, model_axis, split_axis=0,
                                  concat_axis=0, tiled=False)   # (tp,E_l,C,d)
        back = back.reshape(E, C, d)
        y = jnp.einsum("ecd,gtec->gtd", back.astype(jnp.float32),
                       combine)[0].reshape(Bl, Sl, d).astype(x_l.dtype)
        if shared:
            shared_wi, shared_wo = shared
            hs = x_l @ shared_wi
            gs, us = jnp.split(hs, 2, axis=-1)
            y = y + (jax.nn.silu(gs.astype(jnp.float32)).astype(x_l.dtype)
                     * us) @ shared_wo
        return y, jax.lax.pmean(aux, all_axes)

    in_specs = [
        P(data_axes, seq_spec, None),          # x: tokens over data(×model)
        P(None, None),                         # router replicated
        P(model_axis, None, None),             # wi: experts over model
        P(model_axis, None, None),             # wo
    ]
    args = [x, p.router, p.wi, p.wo]
    if p.shared_wi is not None:
        in_specs += [P(None, None), P(None, None)]
        args += [p.shared_wi, p.shared_wo]
    out_specs = (P(data_axes, seq_spec, None), P())
    fn = jax.shard_map(
        local, mesh=mesh, in_specs=tuple(in_specs), out_specs=out_specs,
        check_vma=False)
    y, aux = fn(*args)
    return y, aux
