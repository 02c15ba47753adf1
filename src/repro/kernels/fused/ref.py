"""Pure-jnp oracles for the fused extraction megakernel.

A direct, obviously-correct jax formulation of the state the Pallas
program threads through VMEM/SMEM and across calls.  ``fused_scan_ref`` is
one ``lax.scan`` over trace positions carrying (branch table, address
queue, fill counter) with the state in and out explicit;
``branch_history_scan_ref`` / ``memdist_delta_scan_ref`` are each scan on
its own over a whole trace.  The executable *NumPy* specification remains
``core.features.extract_features_reference``; these oracles mirror the
per-position scan semantics in jax so kernel tests compare like with like:
raw memory-distance deltas (the signed-log runs after the kernel).
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "branch_history_scan_ref",
    "fused_scan_ref",
    "init_state_ref",
    "memdist_delta_scan_ref",
]


def init_state_ref(n_buckets: int, n_queue: int, n_mem: int) -> Tuple:
    return (
        jnp.zeros((n_buckets, n_queue), jnp.float32),  # branch table
        jnp.zeros((n_mem,), jnp.int32),                # address queue
        jnp.int32(0),                                   # fill counter
    )


@functools.partial(jax.jit, static_argnames=("n_mem",))
def fused_scan_ref(
    bucket: jnp.ndarray,   # (n,) int32
    addr: jnp.ndarray,     # (n,) int32
    outcome: jnp.ndarray,  # (n,) f32 in {-1, 0, +1}
    mem: jnp.ndarray,      # (n,) int32 (0/1)
    state: Tuple,          # (table, queue, filled) from init_state_ref
    *,
    n_mem: int,
) -> Tuple[Dict[str, jnp.ndarray], Tuple]:
    """Both scans in one walk, state threaded explicitly: returns
    ``({"brhist": (n, n_queue), "memdist_raw": (n, n_mem)}, new_state)``."""

    def step(carry, x):
        table, queue, filled = carry
        b, a, o, m = x
        is_br = o != 0.0
        row = table[b]
        br_out = jnp.where(is_br, row, 0.0)
        table = table.at[b].set(
            jnp.where(is_br, jnp.concatenate([o[None], row[:-1]]), row)
        )
        is_mem = m != 0
        valid = (jnp.arange(n_mem) < filled) & is_mem
        md_out = jnp.where(valid, (a - queue).astype(jnp.float32), 0.0)
        queue = jnp.where(
            is_mem, jnp.concatenate([a[None], queue[:-1]]), queue
        )
        filled = jnp.where(is_mem, jnp.minimum(filled + 1, n_mem), filled)
        return (table, queue, filled), (br_out, md_out)

    state, (brhist, memdist) = jax.lax.scan(
        step, state, (bucket, addr, outcome, mem)
    )
    return {"brhist": brhist, "memdist_raw": memdist}, state


@functools.partial(jax.jit, static_argnames=("n_buckets", "n_queue"))
def branch_history_scan_ref(
    bucket: jnp.ndarray,   # (n,) int32
    outcome: jnp.ndarray,  # (n,) f32 in {-1, 0, +1}
    *,
    n_buckets: int,
    n_queue: int,
) -> jnp.ndarray:
    """(n, n_queue) f32 — each branch's bucket queue before its own push."""

    def step(table, bo):
        b, o = bo
        is_br = o != 0.0
        row = table[b]
        out = jnp.where(is_br, row, 0.0)
        pushed = jnp.concatenate([o[None], row[:-1]])
        table = table.at[b].set(jnp.where(is_br, pushed, row))
        return table, out

    init = jnp.zeros((n_buckets, n_queue), jnp.float32)
    _, rows = jax.lax.scan(step, init, (bucket, outcome))
    return rows


@functools.partial(jax.jit, static_argnames=("n_mem",))
def memdist_delta_scan_ref(
    addr: jnp.ndarray,  # (n,) int32
    mem: jnp.ndarray,   # (n,) int32 (0/1)
    *,
    n_mem: int,
) -> jnp.ndarray:
    """(n, n_mem) f32 — raw address deltas vs the previous n_mem accesses."""

    def step(carry, am):
        queue, filled = carry
        a, m = am
        is_mem = m != 0
        valid = (jnp.arange(n_mem) < filled) & is_mem
        out = jnp.where(valid, (a - queue).astype(jnp.float32), 0.0)
        pushed = jnp.concatenate([a[None], queue[:-1]])
        queue = jnp.where(is_mem, pushed, queue)
        filled = jnp.where(is_mem, jnp.minimum(filled + 1, n_mem), filled)
        return (queue, filled), out

    init = (jnp.zeros((n_mem,), jnp.int32), jnp.int32(0))
    _, rows = jax.lax.scan(step, init, (addr, mem))
    return rows
