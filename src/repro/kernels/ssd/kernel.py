"""Pallas TPU kernel for the Mamba-2 SSD (state-space dual) chunked scan.

Grid: (B, H, num_chunks) with the chunk dimension "arbitrary" (sequential);
the (N, P) inter-chunk state lives in VMEM scratch and is carried across
chunk steps — the recurrent half of SSD.  Within a chunk the quadratic
(attention-like) form runs on the MXU:

    y_diag = (L ⊙ (C Bᵀ)) diag(dt) X          (c×c masked matmul)
    y_off  = exp(cums) ⊙ (C · state)
    state' = state · exp(cums_last) + Bᵀ diag(dt·decay_to_end) X

Chunk length and head_dim tiles are chosen MXU-friendly (multiples of 128
on the contraction dims where the config allows).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ssd_kernel", "ssd_pallas"]


def ssd_kernel(
    x_ref,     # (1, c, 1, P)
    dt_ref,    # (1, c, 1)
    a_ref,     # (1,)  decay rate for this head (negative)
    b_ref,     # (1, c, 1, N)
    c_ref,     # (1, c, 1, N)
    y_ref,     # out (1, c, 1, P)
    state_scr,  # VMEM (N, P) f32
    *,
    chunk: int,
):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0, :, 0, :].astype(jnp.float32)      # (c, P)
    dt = dt_ref[0, :, 0].astype(jnp.float32)       # (c,)
    a = a_ref[0].astype(jnp.float32)               # scalar (negative)
    Bm = b_ref[0, :, 0, :].astype(jnp.float32)     # (c, N)
    Cm = c_ref[0, :, 0, :].astype(jnp.float32)     # (c, N)

    dA = dt * a                                    # (c,)
    cums = jnp.cumsum(dA)                          # (c,)

    # intra-chunk quadratic term
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    L = jnp.where(ii >= jj, jnp.exp(cums[:, None] - cums[None, :]), 0.0)
    s = jax.lax.dot_general(
        Cm, Bm, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                              # (c, c)
    w = s * L * dt[None, :]
    y = jax.lax.dot_general(
        w, x, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )                                              # (c, P)

    # inter-chunk contribution from the carried state
    state = state_scr[...]                         # (N, P)
    y += jnp.exp(cums)[:, None] * jax.lax.dot_general(
        Cm, state, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    # state update
    decay_to_end = jnp.exp(cums[-1] - cums)        # (c,)
    bw = Bm * (dt * decay_to_end)[:, None]         # (c, N)
    new_state = state * jnp.exp(cums[-1]) + jax.lax.dot_general(
        bw, x, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    state_scr[...] = new_state
    y_ref[0, :, 0, :] = y.astype(y_ref.dtype)


def ssd_pallas(
    xh: jnp.ndarray,   # (B, S, H, P)
    dt: jnp.ndarray,   # (B, S, H)
    A: jnp.ndarray,    # (H,)
    Bm: jnp.ndarray,   # (B, S, G, N)
    Cm: jnp.ndarray,   # (B, S, G, N)
    *,
    chunk: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    B, S, H, P = xh.shape
    G, N = Bm.shape[2], Bm.shape[3]
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk
    rep = H // G

    kernel = functools.partial(ssd_kernel, chunk=chunk)
    out = pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, 1, P), lambda b, h, ci: (b, ci, h, 0)),
            pl.BlockSpec((1, chunk, 1), lambda b, h, ci: (b, ci, h)),
            pl.BlockSpec((1,), lambda b, h, ci: (h,)),
            pl.BlockSpec((1, chunk, 1, N), lambda b, h, ci, _r=rep: (b, ci, h // _r, 0)),
            pl.BlockSpec((1, chunk, 1, N), lambda b, h, ci, _r=rep: (b, ci, h // _r, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, 1, P), lambda b, h, ci: (b, ci, h, 0)),
        out_shape=jax.ShapeDtypeStruct((B, S, H, P), xh.dtype),
        scratch_shapes=[_vmem((N, P))],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        )
        if not interpret
        else None,
        interpret=interpret,
    )(xh, dt, A, Bm, Cm)
    return out


def _vmem(shape):
    return pltpu.VMEM(shape, jnp.float32)
