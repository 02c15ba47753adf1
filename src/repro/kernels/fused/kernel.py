"""Pallas TPU megakernel: raw trace columns -> every model input, one pass.

The staged ``"pallas"`` feature backend runs four device stages per trace —
a fused per-instruction jit (regbits/flags/outcome/mem), the branch-history
scan, the memory-distance scan, and the eager signed-log — and materializes
the full (n, 32 + flags + N_q + N_m) float32 FeatureSet in HBM before the
model's embedding stack reads it back.  At simulation batch sizes that
round-trip is the bandwidth bill (see docs/kernels.md).

This kernel collapses the three in-jit stages into ONE ``pallas_call`` whose
grid walks trace chunks sequentially ("arbitrary" dimension semantics):

  * the vectorized per-instruction work (register bitmap via iota compare,
    the 5-wide flag stack) runs per chunk on a (chunk, 8) int32 VMEM block
    of the raw columns;
  * the two sequential scans walk the chunk one position at a time, reading
    their scalars from SMEM column blocks and pushing into the carried
    (N_b, N_q) branch-outcome table and the N_m-deep address queue — the
    same push steps as the staged kernels (``kernels/features/kernel``).

Feature rows exist only at batch granularity: the caller
(``ops._fused_padded``, driven by ``ops.FusedExtractor``) runs this kernel
on one batch of raw columns inside one compiled program and feeds the
result straight to the engine's jitted step — the O(trace) HBM FeatureSet
never exists.

The scan state is threaded ACROSS calls: the carry table/queue enter as
inputs and leave as outputs.  The state outputs map to the same block on
every grid step, so they stay resident in VMEM for the whole grid; step 0
copies the incoming carry into them and every later step updates them in
place.  Batch k+1 thus continues exactly where batch k stopped, which is
what lets a whole trace stream through fixed-size launches and equal one
monolithic scan.

Memory-distance deltas are RAW int32 subtractions cast to float32, exactly
like the staged kernel: the signed-log compression runs after the kernel,
in the same compiled program, behind ``signed_log_device``'s rounding
barriers (``kernels/features/ops``).

Off-TPU the same program runs under ``interpret=True`` (CPU CI).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..features.kernel import SEQUENTIAL, push_branch, push_mem, smem_column

__all__ = ["VCOLS", "fused_feature_kernel", "fused_feature_pallas"]

# lanes of the (chunk, 8) per-instruction VMEM block, in order
VCOLS = ("opcode", "dst", "src1", "src2", "is_branch", "taken", "is_mem", "is_store")


def fused_feature_kernel(
    bucket_ref,    # SMEM (1, chunk) int32 — (pc >> 2) % N_b
    addr_ref,      # SMEM (1, chunk) int32 — byte address (|addr| < 2^30)
    outcome_ref,   # SMEM (1, chunk) f32 — +1 taken / -1 not-taken / 0
    mem_ref,       # SMEM (1, chunk) int32 — 1 on memory ops
    cols_ref,      # (chunk, 8) int32 — the VCOLS columns
    table_in_ref,  # (n_buckets, lanes(n_queue)) f32 — incoming carry
    queue_in_ref,  # (2, lanes(n_mem)) int32 — incoming carry
    regbits_ref,   # out (chunk, num_regs) f32
    flags_ref,     # out (chunk, n_flags) f32
    brhist_ref,    # out (chunk, n_queue) f32
    memdist_ref,   # out (chunk, n_mem) f32 — RAW deltas (signed-log later)
    table_ref,     # out (n_buckets, lanes(n_queue)) f32 — resident carry
    queue_ref,     # out (2, lanes(n_mem)) int32 — resident carry
    *,
    chunk: int,
    n_queue: int,
    n_mem: int,
    fp_ops: Tuple[int, ...],
):
    @pl.when(pl.program_id(0) == 0)
    def _load_state():
        table_ref[...] = table_in_ref[...]
        queue_ref[...] = queue_in_ref[...]

    # ---- per-instruction features: vectorized over the whole chunk ----
    # (exact integer/bool -> {0.0, 1.0} casts; any compute path is bitwise
    # identical to the staged _per_instruction_device jit)
    v = cols_ref[...]
    op, dst, s1, s2, br, tk, mm, st = (v[:, j : j + 1] for j in range(len(VCOLS)))
    reg = jax.lax.broadcasted_iota(jnp.int32, regbits_ref.shape, 1)
    regbits_ref[...] = ((reg == dst) | (reg == s1) | (reg == s2)).astype(jnp.float32)
    is_fp = op == fp_ops[0]
    for c in fp_ops[1:]:
        is_fp |= op == c
    lane = jax.lax.broadcasted_iota(jnp.int32, flags_ref.shape, 1)
    flags = jnp.zeros(flags_ref.shape, jnp.bool_)
    for k, bit in enumerate((br != 0, tk != 0, mm != 0, st != 0, is_fp)):
        flags |= (lane == k) & bit
    flags_ref[...] = flags.astype(jnp.float32)

    # ---- the two sequential scans, interleaved in one walk ----
    brhist_ref[...] = jnp.zeros_like(brhist_ref)
    memdist_ref[...] = jnp.zeros_like(memdist_ref)

    def body(i, carry):
        o = outcome_ref[0, i]

        @pl.when(o != 0.0)
        def _():
            push_branch(table_ref, brhist_ref, i, bucket_ref[0, i], o, n_queue)

        @pl.when(mem_ref[0, i] != 0)
        def _():
            push_mem(queue_ref, memdist_ref, i, addr_ref[0, i], n_mem)

        return carry

    jax.lax.fori_loop(0, chunk, body, 0)


def fused_feature_pallas(
    bucket: jnp.ndarray,   # (nc, 1, chunk) int32
    addr: jnp.ndarray,     # (nc, 1, chunk) int32
    outcome: jnp.ndarray,  # (nc, 1, chunk) f32
    mem: jnp.ndarray,      # (nc, 1, chunk) int32 0/1
    cols: jnp.ndarray,     # (nc * chunk, 8) int32 — VCOLS
    table: jnp.ndarray,    # (n_buckets, lanes(n_queue)) f32 carry in
    queue: jnp.ndarray,    # (2, lanes(n_mem)) int32 carry in
    *,
    n_queue: int,
    n_mem: int,
    n_flags: int,
    num_regs: int,
    fp_ops: Tuple[int, ...],
    interpret: bool = False,
):
    """One fused pass over ``nc * chunk`` trace positions.  Returns
    ``(regbits, flags, brhist, memdist_raw, table_out, queue_out)`` — the
    last two being the scan carry to thread into the next call."""
    nc, _, chunk = bucket.shape
    kernel = functools.partial(
        fused_feature_kernel,
        chunk=chunk,
        n_queue=n_queue,
        n_mem=n_mem,
        fp_ops=fp_ops,
    )

    def rows(width):
        return pl.BlockSpec((chunk, width), lambda c: (c, 0))

    def whole(a):
        return pl.BlockSpec(a.shape, lambda c: (0, 0))

    n = nc * chunk
    return pl.pallas_call(
        kernel,
        grid=(nc,),
        in_specs=[smem_column(chunk)] * 4
        + [rows(cols.shape[1]), whole(table), whole(queue)],
        out_specs=[
            rows(num_regs), rows(n_flags), rows(n_queue), rows(n_mem),
            whole(table), whole(queue),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, num_regs), jnp.float32),
            jax.ShapeDtypeStruct((n, n_flags), jnp.float32),
            jax.ShapeDtypeStruct((n, n_queue), jnp.float32),
            jax.ShapeDtypeStruct((n, n_mem), jnp.float32),
            jax.ShapeDtypeStruct(table.shape, table.dtype),
            jax.ShapeDtypeStruct(queue.shape, queue.dtype),
        ],
        compiler_params=SEQUENTIAL,
        interpret=interpret,
    )(bucket, addr, outcome, mem, cols, table, queue)
