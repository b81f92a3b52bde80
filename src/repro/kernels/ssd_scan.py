"""Mamba2 SSD chunked-scan Pallas TPU kernel.

TPU re-think of the SSD algorithm (DESIGN.md §2): the GPU version keys on
warp-level scans; on TPU the winning decomposition is

  * intra-chunk terms  -> MXU batched matmuls over a (chunk x chunk) tile,
  * inter-chunk terms  -> a VMEM-resident (P x N) running state carried
                          across sequential grid steps (the PLM),

with the chunk length + head blocking chosen by the local-partitioning
pass (``plan.partitions['ssd_scan']``).

Grid: (batch*heads, seq/chunk) — chunk dim sequential (state carry).
Inputs are fp32 (the SSD recurrence is exp-sensitive).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(
    x_ref,        # (1, chunk, P)
    dt_ref,       # (1, chunk, 1)  dt as a column
    cs_ref,       # (1, chunk, 1)  chunk-local cumsum of dt*A, column
    csr_ref,      # (1, 1, 1, chunk) the same cumsum as a row
    b_ref,        # (1, chunk, N)
    c_ref,        # (1, chunk, N)
    y_ref,        # (1, chunk, P)
    state_scr,    # VMEM (P, N) running state
    *,
    chunk: int,
):
    c_idx = pl.program_id(1)

    @pl.when(c_idx == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0]                        # (Q, P)
    dt = dt_ref[0]                      # (Q, 1)
    cs = cs_ref[0]                      # (Q, 1)
    cs_row = csr_ref[0, 0]              # (1, Q)
    Bm = b_ref[0]                       # (Q, N)
    Cm = c_ref[0]                       # (Q, N)

    # 1. intra-chunk: L[q,k] = exp(cs[q]-cs[k]) for k<=q
    qi = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    ki = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    L = jnp.where(qi >= ki, jnp.exp(cs - cs_row), 0.0)
    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    xdt = x * dt
    y = jax.lax.dot_general(scores * L, xdt, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # 2. contribution of the carried-in state
    state = state_scr[...]              # (P, N)
    y += jnp.exp(cs) * jax.lax.dot_general(
        Cm, state, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    # 3. update the state for the next chunk
    # last element via a masked lane reduction: a (1, 1) slice at lane
    # chunk-1 has a layout Mosaic cannot broadcast down the sublanes
    last = jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1) == chunk - 1
    cs_last = jnp.sum(jnp.where(last, cs_row, 0.0), axis=-1,
                      keepdims=True)    # (1, 1)
    decay_out = jnp.exp(cs_last - cs)   # (Q, 1)
    new_contrib = jax.lax.dot_general(
        xdt * decay_out, Bm, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)              # (P, N)
    state_scr[...] = state * jnp.exp(cs_last) + new_contrib

    y_ref[0] = y.astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(
    x: jax.Array,              # (B, S, H, P) fp32
    dt: jax.Array,             # (B, S, H) fp32 (post-softplus)
    A: jax.Array,              # (H,) fp32 negative
    Bm: jax.Array,             # (B, S, H, N) fp32 (groups pre-broadcast)
    Cm: jax.Array,             # (B, S, H, N) fp32
    *,
    chunk: int = 256,
    interpret: bool = False,
) -> jax.Array:
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    chunk = min(chunk, S)
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk

    xg = x.transpose(0, 2, 1, 3).reshape(B * H, S, P)
    dtg = dt.transpose(0, 2, 1).reshape(B * H, S)
    # the chunk-local cumsum of dt*A is an elementwise prefix in XLA;
    # the kernel takes it as a column and as a row so neither needs an
    # in-kernel transpose
    dA = dtg * jnp.broadcast_to(A[None, :], (B, H)).reshape(B * H, 1)
    cs = jnp.cumsum(dA.reshape(B * H, nc, chunk), axis=-1)
    bg = Bm.transpose(0, 2, 1, 3).reshape(B * H, S, N)
    cg = Cm.transpose(0, 2, 1, 3).reshape(B * H, S, N)

    col = lambda b, c: (b, c, 0)
    grid = (B * H, nc)
    out = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, P), col),
            pl.BlockSpec((1, chunk, 1), col),
            pl.BlockSpec((1, chunk, 1), col),
            pl.BlockSpec((1, 1, 1, chunk), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, chunk, N), col),
            pl.BlockSpec((1, chunk, N), col),
        ],
        out_specs=pl.BlockSpec((1, chunk, P), col),
        out_shape=jax.ShapeDtypeStruct((B * H, S, P), jnp.float32),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(xg, dtg[..., None], cs.reshape(B * H, S, 1),
      cs.reshape(B * H, nc, 1, chunk), bg, cg)
    return out.reshape(B, H, S, P).transpose(0, 2, 1, 3)
