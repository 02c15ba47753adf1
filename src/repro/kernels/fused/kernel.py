"""Pallas TPU megakernel: raw trace columns -> every model input, one pass.

The §4.2 features need two sequential scans over the trace (the per-bucket
branch-history table and the memory-distance address queue) besides the
per-instruction register bitmap and flags.  This kernel runs all of it in
ONE ``pallas_call`` whose grid walks trace chunks sequentially ("arbitrary"
dimension semantics):

  * the vectorized per-instruction work (register bitmap via iota compare,
    the 5-wide flag stack) runs per chunk on a (chunk, 8) int32 VMEM block
    of the raw columns;
  * the two sequential scans walk the chunk one position at a time, reading
    their scalars from SMEM column blocks and pushing into the carried
    (N_b, N_q) branch-outcome table and the N_m-deep address queue
    (``push_branch`` / ``push_mem``).

Layout, as Mosaic wants it: the per-position scalars the scans branch and
index on (bucket, outcome, address, mem bit) arrive as SMEM blocks and
are read one scalar at a time; every queue lives in a row padded to whole
128-lane vregs, so a push is a static one-lane ``pltpu.roll`` plus a select
on lane 0.  The padding lanes past N_q / N_m hold stale values that only
ever move further right, so they never reach an emitted lane.  The table
row is a dynamic-sublane load/store (``pl.ds(bucket, 1)``).

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

Memory-distance deltas leave the kernel RAW (int32 subtraction is exact;
int->float32 conversion is correctly rounded): the signed-log compression
runs after the kernel, in the same compiled program, behind
``ops.signed_log_device``'s rounding barriers.

Off-TPU the same program runs under ``interpret=True`` (CPU CI).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "SEQUENTIAL",
    "VCOLS",
    "fused_feature_kernel",
    "fused_feature_pallas",
    "lanes",
    "push_branch",
    "push_mem",
    "smem_column",
]

LANES = 128


def lanes(n: int) -> int:
    """``n`` rounded up to whole 128-lane vregs (the padded queue width)."""
    return -(-n // LANES) * LANES


# sequential ("arbitrary") grid: scratch state flows chunk to chunk
SEQUENTIAL = pltpu.CompilerParams(dimension_semantics=("arbitrary",))


def _push(row, head):
    """Shift a padded (r, lanes) queue one lane right, ``head`` into lane 0."""
    lane = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1)
    return jnp.where(lane == 0, head, pltpu.roll(row, 1, 1))


def push_branch(table_scr, out_ref, i, b, o, n_queue: int):
    """Branch at position ``i`` in bucket ``b`` with outcome ``o`` (±1):
    emit the bucket's queue as feature row ``i``, then push ``o``."""
    row = table_scr[pl.ds(b, 1), :]                       # (1, lanes(N_q))
    out_ref[pl.ds(i, 1), :] = row[:, :n_queue]
    table_scr[pl.ds(b, 1), :] = _push(row, o)


def push_mem(queue_scr, out_ref, i, a, n_mem: int):
    """Memory access at position ``i`` to address ``a``: emit the raw
    deltas against the filled queue slots, then push ``a``.  ``queue_scr``
    is (2, lanes(N_m)) int32: row 0 the addresses, row 1 the filled bits."""
    q = queue_scr[...]
    delta = (a - q[0:1, :]).astype(jnp.float32)           # exact int32 sub
    out_ref[pl.ds(i, 1), :] = jnp.where(q[1:2, :] != 0, delta, 0.0)[:, :n_mem]
    sub = jax.lax.broadcasted_iota(jnp.int32, q.shape, 0)
    queue_scr[...] = _push(q, jnp.where(sub == 0, a, 1))


def smem_column(chunk: int) -> pl.BlockSpec:
    """One chunk of an (nc, 1, chunk) per-position column, as SMEM scalars.
    (A 1-D column would need 1024-position blocks to match XLA's SMEM
    tiling, and an (nc, chunk) one breaks the (8, 128) block rule.)"""
    return pl.BlockSpec((None, 1, chunk), lambda c: (c, 0, 0), memory_space=pltpu.SMEM)


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
    # identical to the NumPy extraction)
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
