"""Pallas TPU flash-attention kernel.

Grid: (B, H, num_q_blocks, num_k_blocks) with the k dimension marked
"arbitrary" (sequential) so the online-softmax state (m, l, acc) lives in
VMEM scratch across k steps.  Block shapes are (block_q, head_dim) /
(block_k, head_dim) tiles staged HBM->VMEM by BlockSpec; head_dim and the
block sizes are multiples of 128 to keep the MXU fully utilized.

Causal masking happens at two granularities:

  * **static** — ``q_offset`` and the sequence lengths are trace-time
    constants, so k-blocks that sit entirely above the causal diagonal for
    EVERY q-block (``first_k > q_offset + Sq - 1``) are clamped out of the
    grid itself and never scheduled (zero DMA, zero FLOPs);
  * **dynamic** — within the clamped grid, a per-tile ``pl.when``
    predicate skips the remaining fully-masked (qi, ki) tiles of the
    triangular schedule, and the in-tile position mask handles the
    diagonal blocks element-wise.

Optional ``segment_ids`` fold a per-tile segment-equality mask into the
position mask so windows packed back-to-back in one sequence never attend
across their boundary (the fused backend's batched-window layout).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_kernel", "flash_attention_pallas"]

NEG_INF = -1e30


def flash_attention_kernel(
    *refs,
    block_q: int,
    block_k: int,
    seq_k: int,
    causal: bool,
    q_offset: int,
    scale: float,
    segmented: bool,
):
    if segmented:
        q_ref, k_ref, v_ref, segq_ref, segk_ref, o_ref = refs[:6]
        m_scr, l_scr, acc_scr = refs[6:]
    else:
        q_ref, k_ref, v_ref, o_ref = refs[:4]
        m_scr, l_scr, acc_scr = refs[4:]
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_pos = q_offset + qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )

    # Fully-above-diagonal tiles: k-blocks masked for EVERY q-block were
    # already clamped out of the grid (static, see flash_attention_pallas);
    # the interior triangular skip depends on qi/ki — grid indices — so it
    # is necessarily a dynamic per-tile predicate.
    last_q = q_offset + qi * block_q + (block_q - 1)
    first_k = ki * block_k
    run = (last_q >= first_k) if causal else (ki >= 0)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)          # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)          # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                     # (bq, bk)
        mask = k_pos < seq_k
        if causal:
            mask &= q_pos >= k_pos
        if segmented:
            sq = segq_ref[0]                          # (bq,)
            sk = segk_ref[0]                          # (bk,)
            mask &= sq[:, None] == sk[None, :]
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                           # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)           # (bk, dv)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc_scr[...] = acc_scr[...] * corr + pv
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_scr[...]
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    segment_ids: jnp.ndarray | None = None,
    *,
    causal: bool = True,
    q_offset: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """q,k,v: (B, H, S, D) (GQA already expanded).  Returns (B, H, Sq, D).

    ``segment_ids``: optional (B, Sk) int32 — positions only attend within
    their own segment (q rows take theirs from ``q_offset + row``, so
    ``Sq < Sk`` decode-style calls work too).
    """
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    Dv = v.shape[3]
    scale = 1.0 / math.sqrt(D)
    bq = min(block_q, max(Sq, 8))
    bk = min(block_k, max(Sk, 8))
    nq = -(-Sq // bq)
    nk = -(-Sk // bk)
    if causal:
        # Static diagonal clamp: q_offset/Sq/bk are trace-time ints, so
        # k-blocks past the last query position (first_k > q_offset+Sq-1,
        # i.e. masked for ALL q-blocks) are simply never part of the grid.
        nk = max(1, min(nk, -(-(q_offset + Sq) // bk)))
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, nq * bq - Sq), (0, 0)))
    # the clamp may leave nk*bk < Sk — those key blocks are dead for every
    # query, so slice them off (pad only when rounding UP to the tile edge)
    kv_len = nk * bk
    if kv_len >= Sk:
        kp = jnp.pad(k, ((0, 0), (0, 0), (0, kv_len - Sk), (0, 0)))
        vp = jnp.pad(v, ((0, 0), (0, 0), (0, kv_len - Sk), (0, 0)))
    else:
        kp = k[:, :, :kv_len]
        vp = v[:, :, :kv_len]

    segmented = segment_ids is not None
    kernel = functools.partial(
        flash_attention_kernel,
        block_q=bq,
        block_k=bk,
        seq_k=Sk,
        causal=causal,
        q_offset=q_offset,
        scale=scale,
        segmented=segmented,
    )
    in_specs = [
        pl.BlockSpec((1, 1, bq, D), lambda b, h, qi, ki: (b, h, qi, 0)),
        pl.BlockSpec((1, 1, bk, D), lambda b, h, qi, ki: (b, h, ki, 0)),
        pl.BlockSpec((1, 1, bk, Dv), lambda b, h, qi, ki: (b, h, ki, 0)),
    ]
    operands = [qp, kp, vp]
    if segmented:
        if segment_ids.shape != (B, Sk):
            raise ValueError(
                f"segment_ids must be (B, Sk)=({B}, {Sk}), got "
                f"{segment_ids.shape}"
            )
        seg = segment_ids.astype(jnp.int32)
        # q rows read segment ids at their absolute positions; distinct
        # sentinels on the two pads keep padded rows from ever matching
        segq = jax.lax.dynamic_slice_in_dim(
            jnp.pad(seg, ((0, 0), (0, max(0, q_offset + Sq - Sk))),
                    constant_values=-2),
            q_offset, Sq, axis=1,
        )
        segq = jnp.pad(segq, ((0, 0), (0, nq * bq - Sq)), constant_values=-2)
        if kv_len >= Sk:
            segk = jnp.pad(seg, ((0, 0), (0, kv_len - Sk)), constant_values=-1)
        else:
            segk = seg[:, :kv_len]
        in_specs += [
            pl.BlockSpec((1, bq), lambda b, h, qi, ki: (b, qi)),
            pl.BlockSpec((1, bk), lambda b, h, qi, ki: (b, ki)),
        ]
        operands += [segq, segk]
    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, bq, Dv), lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, nq * bq, Dv), q.dtype),
        scratch_shapes=[
            pltpu_scratch((bq, 1)),
            pltpu_scratch((bq, 1)),
            pltpu_scratch((bq, Dv)),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")
        )
        if not interpret
        else None,
        interpret=interpret,
    )(*operands)
    return out[:, :, :Sq, :]


def pltpu_scratch(shape):
    """VMEM f32 scratch allocation (TPU memory space)."""
    return pltpu.VMEM(shape, jnp.float32)
