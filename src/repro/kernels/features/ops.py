"""Public wrappers for the §4.2 feature-extraction Pallas kernels.

On TPU the kernels lower natively through Mosaic; everywhere else they run
under ``interpret=True`` so CPU CI exercises the same programs.  The
contract — enforced on the CPU backend by ``tests/test_feature_kernels.py``
— is that the device extraction is **bit-identical** to the NumPy
specification (``core.features.extract_features`` /
``extract_features_reference``); on the TPU everything but the signed-log
values stays bit-exact (docs/kernels.md "Exactness"):

  * branch-history rows are copies of {-1, 0, +1} values (exact);
  * memory-distance deltas are int32 subtractions (exact) converted to
    float32 (correctly rounded), with the signed-log compression applied by
    ``signed_log_device`` — the jax twin of ``core.features.signed_log``.
    An integer rounding barrier after each product keeps XLA from
    contracting `a*b + c` into an fma (one rounding instead of two), so
    the same function serves the fused backend's compiled program and the
    staged backend's eager calls.

``trace_columns`` does the cheap host-side prep (bucket hash on the int64
pc, int32 address narrowing) and raises ``ValueError`` when addresses fall
outside the int32-exact window: a device feature path never silently
becomes the NumPy one.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ...compat import on_tpu
from ...core import features as _features
from ...core.features import (
    SIGNED_LOG_COEFFS,
    SIGNED_LOG_SQRT2,
    FeatureConfig,
    FeatureSet,
)
from ...uarch.isa import NUM_REGS, Op
from .kernel import branch_history_pallas, memdist_delta_pallas

__all__ = [
    "signed_log_device",
    "branch_history_scan",
    "memdist_delta_scan",
    "trace_columns",
    "device_feature_arrays",
    "extract_features_device",
    "ADDR_EXACT_LIMIT",
]

# Addresses must stay within this bound for int32 deltas to be exact (and
# overflow-free: |a - b| < 2^31 when |a|, |b| < 2^30).
ADDR_EXACT_LIMIT = 2**30

DEFAULT_CHUNK = 512


def _rounded(p: jnp.ndarray, zero) -> jnp.ndarray:
    """``p`` as the float32 it was rounded to, before any later op reads it.

    ``zero`` is an int32 zero the compiler cannot see (a traced argument
    inside a compiled program): OR-ing it into ``p``'s bits is the
    identity, but XLA can no longer contract the multiply that made ``p``
    into the add that reads it."""
    bits = jax.lax.bitcast_convert_type(p, jnp.int32) | zero
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


# tao: bitwise
def signed_log_device(d: jnp.ndarray, zero) -> jnp.ndarray:
    """Bit-exact jax twin of ``core.features.signed_log``.

    The same chain of float32 ops, with every product that feeds an add
    (``s * s``, each Horner ``p * z``, ``p * s``) rounded through
    ``_rounded``.  ``zero`` is an int32 zero, traced inside a compiled
    program: ``jax.jit(signed_log_device)(d, np.int32(0))`` equals NumPy
    bit for bit on the CPU, as does an eager call (``core.features``
    states the decision).
    """
    d = jnp.asarray(d, jnp.float32)
    a = jnp.abs(d)
    x = jnp.float32(1.0) + a
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    e = ((bits >> 23) & jnp.int32(0xFF)) - jnp.int32(127)
    m = jax.lax.bitcast_convert_type(
        (bits & jnp.int32(0x007FFFFF)) | jnp.int32(0x3F800000), jnp.float32
    )
    big = m > SIGNED_LOG_SQRT2
    m = jnp.where(big, m * jnp.float32(0.5), m)  # exact: no rounding to pin
    e = (e + big).astype(jnp.float32)
    s = (m - jnp.float32(1.0)) / (m + jnp.float32(1.0))
    z = s * s
    z = _rounded(z, zero)
    p = jnp.full_like(z, SIGNED_LOG_COEFFS[-1])
    for c in SIGNED_LOG_COEFFS[-2::-1]:
        p = p * z
        p = _rounded(p, zero)
        p = p + jnp.float32(c)
    r = p * s
    r = _rounded(r, zero)
    r = r + e
    r = r * jnp.float32(1.0 / 32.0)
    return jnp.where(d < 0, -r, r)


def chunked_column(v: jnp.ndarray, chunk: int) -> jnp.ndarray:
    """(n,) per-position column -> zero-padded (nc, 1, chunk), the layout
    of the kernels' SMEM column blocks (pad rows are non-branch, non-mem:
    the scan state passes through them untouched)."""
    n = v.shape[0]
    nc = max(1, -(-n // chunk))
    return jnp.pad(v, (0, nc * chunk - n)).reshape(nc, 1, chunk)


def kernel_chunk(chunk: int) -> int:
    """The grid chunk rounded up to whole 8-row sublane tiles, as Mosaic
    wants the (chunk, F) output blocks; results do not depend on it."""
    return -(-chunk // 8) * 8


@functools.partial(
    jax.jit, static_argnames=("n_buckets", "n_queue", "chunk", "interpret")
)
def _branch_history_padded(bucket, outcome, *, n_buckets, n_queue, chunk, interpret):
    out = branch_history_pallas(
        chunked_column(bucket, chunk),
        chunked_column(outcome, chunk),
        n_buckets=n_buckets,
        n_queue=n_queue,
        interpret=interpret,
    )
    return out[: bucket.shape[0]]


def branch_history_scan(
    bucket,
    outcome,
    *,
    n_buckets: int,
    n_queue: int,
    chunk: int = DEFAULT_CHUNK,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """(n,) bucket ids + outcomes -> (n, n_queue) branch-history features."""
    if interpret is None:
        interpret = not on_tpu()
    bucket = jnp.asarray(bucket, jnp.int32)
    outcome = jnp.asarray(outcome, jnp.float32)
    if bucket.shape[0] == 0:
        return jnp.zeros((0, n_queue), jnp.float32)
    return _branch_history_padded(
        bucket,
        outcome,
        n_buckets=n_buckets,
        n_queue=n_queue,
        chunk=kernel_chunk(chunk),
        interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("n_mem", "chunk", "interpret"))
def _memdist_padded(addr, mem, *, n_mem, chunk, interpret):
    out = memdist_delta_pallas(
        chunked_column(addr, chunk),
        chunked_column(mem, chunk),
        n_mem=n_mem,
        interpret=interpret,
    )
    return out[: addr.shape[0]]


def memdist_delta_scan(
    addr,
    mem,
    *,
    n_mem: int,
    chunk: int = DEFAULT_CHUNK,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """(n,) int32 addresses + mem mask -> (n, n_mem) RAW float32 deltas."""
    if interpret is None:
        interpret = not on_tpu()
    addr = jnp.asarray(addr, jnp.int32)
    mem = jnp.asarray(mem, jnp.int32)
    if addr.shape[0] == 0:
        return jnp.zeros((0, n_mem), jnp.float32)
    return _memdist_padded(
        addr, mem, n_mem=n_mem, chunk=kernel_chunk(chunk), interpret=interpret
    )


def trace_columns(trace: np.ndarray, cfg: FeatureConfig) -> Dict[str, np.ndarray]:
    """Host-side prep of the device extraction inputs.

    Bucket hashing runs on the host so the int64 pc is handled exactly;
    everything shipped to the device is int32/float32.  Raises ValueError
    when addresses exceed the int32-exact window (|addr| >= 2^30): the
    device deltas would be inexact, and only the NumPy backend
    (``extract_features``) handles such traces.
    """
    addr = trace["addr"]
    if len(addr) and int(np.abs(addr).max()) >= ADDR_EXACT_LIMIT:
        raise ValueError(
            f"trace addresses exceed |addr| < 2^30 (= {ADDR_EXACT_LIMIT}); "
            "int32 device deltas would be inexact — use the NumPy feature "
            "path (extract_features / feature_backend='numpy')"
        )
    # Minimal payload (~28 B/instr): branch outcomes and the mem mask are
    # derived on device from the bool columns instead of being shipped as
    # widened duplicates.
    return {
        "bucket": ((trace["pc"] >> 2) % cfg.n_buckets).astype(np.int32),
        "addr": addr.astype(np.int32),
        "opcode": trace["opcode"].astype(np.int32),
        "dst": trace["dst"].astype(np.int32),
        "src1": trace["src1"].astype(np.int32),
        "src2": trace["src2"].astype(np.int32),
        "is_branch": trace["is_branch"],
        "taken": trace["taken"],
        "is_mem": trace["is_mem"],
        "is_store": trace["is_store"],
    }


@jax.jit
def _per_instruction_device(opcode, dst, src1, src2, is_branch, taken, is_mem, is_store):
    # Exact integer/boolean -> float32 ops only: safe to fuse in one jit.
    reg = jnp.arange(NUM_REGS, dtype=jnp.int32)[None, :]
    regbits = (
        (reg == dst[:, None]) | (reg == src1[:, None]) | (reg == src2[:, None])
    ).astype(jnp.float32)
    is_fp = (
        (opcode == int(Op.FALU)) | (opcode == int(Op.FMUL)) | (opcode == int(Op.FDIV))
    )
    flags = jnp.stack(
        [is_branch, taken, is_mem, is_store, is_fp], axis=1
    ).astype(jnp.float32)
    # Scan-kernel inputs derived on device (exact selects/casts): ±1/0
    # branch outcomes and the int32 mem mask.
    outcome = jnp.where(
        is_branch,
        jnp.where(taken, jnp.float32(1.0), jnp.float32(-1.0)),
        jnp.float32(0.0),
    )
    mem = is_mem.astype(jnp.int32)
    return regbits, flags, outcome, mem


def device_feature_arrays(
    cols: Dict[str, np.ndarray],
    cfg: FeatureConfig,
    *,
    chunk: int = DEFAULT_CHUNK,
    interpret: Optional[bool] = None,
) -> Dict[str, jnp.ndarray]:
    """Run the full device extraction; returns (n, ·) jnp arrays keyed like
    ``core.dataset.INPUT_KEYS``, plus the device-resident ``is_branch`` /
    ``is_mem`` bool columns so callers (the engine's device batch path)
    never re-upload them.  All values stay on device."""
    is_branch = jnp.asarray(cols["is_branch"])
    is_mem = jnp.asarray(cols["is_mem"])
    regbits, flags, outcome, mem = _per_instruction_device(
        jnp.asarray(cols["opcode"]),
        jnp.asarray(cols["dst"]),
        jnp.asarray(cols["src1"]),
        jnp.asarray(cols["src2"]),
        is_branch,
        jnp.asarray(cols["taken"]),
        is_mem,
        jnp.asarray(cols["is_store"]),
    )
    brhist = branch_history_scan(
        cols["bucket"],
        outcome,
        n_buckets=cfg.n_buckets,
        n_queue=cfg.n_queue,
        chunk=chunk,
        interpret=interpret,
    )
    deltas = memdist_delta_scan(
        cols["addr"],
        mem,
        n_mem=cfg.n_mem,
        chunk=chunk,
        interpret=interpret,
    )
    memdist = signed_log_device(deltas, np.int32(0))
    return {
        "opcode": jnp.asarray(cols["opcode"], jnp.int32),
        "regbits": regbits,
        "flags": flags,
        "brhist": brhist,
        "memdist": memdist,
        "is_branch": is_branch,
        "is_mem": is_mem,
    }


def extract_features_device(
    trace: np.ndarray,
    cfg: FeatureConfig = FeatureConfig(),
    with_labels: bool = True,
    *,
    chunk: int = DEFAULT_CHUNK,
    interpret: Optional[bool] = None,
) -> FeatureSet:
    """Drop-in twin of ``core.features.extract_features`` backed by the
    Pallas kernels; raises ValueError when addresses exceed the int32-exact
    window (use the NumPy extractor there)."""
    cols = trace_columns(trace, cfg)
    arrays = device_feature_arrays(cols, cfg, chunk=chunk, interpret=interpret)
    return FeatureSet(
        opcode=np.asarray(arrays["opcode"]),
        regbits=np.asarray(arrays["regbits"]),
        flags=np.asarray(arrays["flags"]),
        brhist=np.asarray(arrays["brhist"]),
        memdist=np.asarray(arrays["memdist"]),
        labels=_features._labels(trace, with_labels),
    )
