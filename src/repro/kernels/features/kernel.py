"""Pallas TPU kernels for §4.2 cross-instruction feature extraction.

Both kernels are sequential scans over the trace, gridded over trace chunks
with the recurrent state carried in VMEM scratch across grid steps — the
same chunk-carry pattern as the SSD kernel (``kernels/ssd/kernel.py``):

  * **branch history** — the (N_b, N_q) per-bucket outcome table lives in
    VMEM scratch; each branch reads its bucket's queue (the feature row),
    then pushes its outcome most-recent-first.  Non-branch positions leave
    the table untouched and emit a zero row.
  * **memory distance** — the last N_m access addresses live in an int32
    VMEM queue, with a second row marking which slots hold real addresses.
    Each memory access emits the raw address deltas against the queue;
    non-memory positions emit zeros.

Layout, as Mosaic wants it: the per-position scalars the scans branch and
index on (bucket, outcome, address, mem bit) arrive as SMEM blocks and
are read one scalar at a time; every queue lives in a row padded to whole
128-lane vregs, so a push is a static one-lane ``pltpu.roll`` plus a select
on lane 0.  The padding lanes past N_q / N_m hold stale values that only
ever move further right, so they never reach an emitted lane.  The table
row is a dynamic-sublane load/store (``pl.ds(bucket, 1)``).  The fused
megakernel (``kernels/fused/kernel.py``) runs the same two push steps.

The memory-distance kernel deliberately returns RAW int32-derived deltas as
float32 (int32 subtraction is exact; int→float32 conversion is correctly
rounded) rather than applying the signed-log compression in-kernel.  The
caller applies ``ops.signed_log_device``, the jax twin of
``core.features.signed_log`` (see the comment there), eagerly on the
staged backend and inside the fused backend's compiled program.  Its
integer rounding barriers keep XLA from contracting `a*b + c` chains into
fma, which would break bit-reproducibility against the NumPy backend.

Grid semantics: the single chunk dimension is "arbitrary" (sequential), so
scratch state flows from chunk to chunk.  Off-TPU the same programs run
under ``interpret=True``, which is how CPU CI exercises them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "branch_history_pallas",
    "lanes",
    "memdist_delta_pallas",
    "push_branch",
    "push_mem",
    "SEQUENTIAL",
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


def branch_history_kernel(
    bucket_ref,   # SMEM (1, chunk) int32 — (pc >> 2) % N_b, any value on pad rows
    outcome_ref,  # SMEM (1, chunk) f32  — +1 taken / -1 not-taken / 0 non-branch
    out_ref,      # out (chunk, n_queue) f32
    table_scr,    # VMEM (n_buckets, lanes(n_queue)) f32 — carried across chunks
    *,
    chunk: int,
    n_queue: int,
):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        table_scr[...] = jnp.zeros_like(table_scr)

    out_ref[...] = jnp.zeros_like(out_ref)

    def body(i, carry):
        o = outcome_ref[0, i]

        @pl.when(o != 0.0)
        def _():
            push_branch(table_scr, out_ref, i, bucket_ref[0, i], o, n_queue)

        return carry

    jax.lax.fori_loop(0, chunk, body, 0)


def memdist_delta_kernel(
    addr_ref,   # SMEM (1, chunk) int32 — byte address, any value on non-mem rows
    mem_ref,    # SMEM (1, chunk) int32 — 1 for memory ops, 0 otherwise
    out_ref,    # out (chunk, n_mem) f32 — raw deltas, 0 on invalid slots
    queue_scr,  # VMEM (2, lanes(n_mem)) int32 — carried across chunks
    *,
    chunk: int,
    n_mem: int,
):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        queue_scr[...] = jnp.zeros_like(queue_scr)

    out_ref[...] = jnp.zeros_like(out_ref)

    def body(i, carry):
        @pl.when(mem_ref[0, i] != 0)
        def _():
            push_mem(queue_scr, out_ref, i, addr_ref[0, i], n_mem)

        return carry

    jax.lax.fori_loop(0, chunk, body, 0)


def smem_column(chunk: int) -> pl.BlockSpec:
    """One chunk of an (nc, 1, chunk) per-position column, as SMEM scalars.
    (A 1-D column would need 1024-position blocks to match XLA's SMEM
    tiling, and an (nc, chunk) one breaks the (8, 128) block rule.)"""
    return pl.BlockSpec((None, 1, chunk), lambda c: (c, 0, 0), memory_space=pltpu.SMEM)


def branch_history_pallas(
    bucket: jnp.ndarray,   # (nc, 1, chunk) int32
    outcome: jnp.ndarray,  # (nc, 1, chunk) f32
    *,
    n_buckets: int,
    n_queue: int,
    interpret: bool = False,
) -> jnp.ndarray:
    nc, _, chunk = bucket.shape
    kernel = functools.partial(branch_history_kernel, chunk=chunk, n_queue=n_queue)
    return pl.pallas_call(
        kernel,
        grid=(nc,),
        in_specs=[smem_column(chunk), smem_column(chunk)],
        out_specs=pl.BlockSpec((chunk, n_queue), lambda c: (c, 0)),
        out_shape=jax.ShapeDtypeStruct((nc * chunk, n_queue), jnp.float32),
        scratch_shapes=[pltpu.VMEM((n_buckets, lanes(n_queue)), jnp.float32)],
        compiler_params=SEQUENTIAL,
        interpret=interpret,
    )(bucket, outcome)


def memdist_delta_pallas(
    addr: jnp.ndarray,  # (nc, 1, chunk) int32
    mem: jnp.ndarray,   # (nc, 1, chunk) int32
    *,
    n_mem: int,
    interpret: bool = False,
) -> jnp.ndarray:
    nc, _, chunk = addr.shape
    kernel = functools.partial(memdist_delta_kernel, chunk=chunk, n_mem=n_mem)
    return pl.pallas_call(
        kernel,
        grid=(nc,),
        in_specs=[smem_column(chunk), smem_column(chunk)],
        out_specs=pl.BlockSpec((chunk, n_mem), lambda c: (c, 0)),
        out_shape=jax.ShapeDtypeStruct((nc * chunk, n_mem), jnp.float32),
        scratch_shapes=[pltpu.VMEM((2, lanes(n_mem)), jnp.int32)],
        compiler_params=SEQUENTIAL,
        interpret=interpret,
    )(addr, mem)
