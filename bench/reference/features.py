"""Plain NumPy feature specification of Tao's inputs (arXiv:2404.10921 §4.2).

Self-contained: it imports nothing of the simulator.  From a functional
trace (a structured array with ``pc``, ``opcode``, ``dst``, ``src1``,
``src2``, ``is_branch``, ``taken``, ``is_mem``, ``is_store``, ``addr``) it
computes, per instruction:

  opcode    int32 id
  regbits   one-hot of src1, src2 and dst over ``num_regs`` registers
  flags     is_branch, taken, is_mem, is_store, is_fp
  brhist    the outcome queue (most recent first; +1 taken, -1 not taken,
            0 empty) of the branch-history table bucket ``(pc >> 2) % n_b``
            that a conditional branch sees before it is pushed
  memdist   signed-log deltas ``sign(d) * log2(1 + |d|) / 32`` between a
            memory access's address and the previous ``n_m`` accesses

The signed log is evaluated as a fixed sequence of individually rounded
float32 operations (exponent split, then an atanh series), the same
sequence the paper repository's NumPy specification uses, so equal inputs
give equal bits.
"""
from __future__ import annotations

import math
from typing import Dict

import numpy as np

# 2/ln2 / k for k = 1, 3, ..., 13: log2(m) = (2/ln2) * atanh(s), s = (m-1)/(m+1)
_COEFFS = tuple(np.float32(2.0 / math.log(2.0) / k) for k in (1, 3, 5, 7, 9, 11, 13))
_SQRT2 = np.float32(math.sqrt(2.0))


def signed_log(d: np.ndarray) -> np.ndarray:
    d = np.asarray(d).astype(np.float32)
    x = np.float32(1.0) + np.abs(d)
    bits = x.view(np.int32)
    e = ((bits >> 23) & np.int32(0xFF)) - np.int32(127)
    m = ((bits & np.int32(0x007FFFFF)) | np.int32(0x3F800000)).view(np.float32)
    big = m > _SQRT2
    m = np.where(big, m * np.float32(0.5), m)
    e = (e + big).astype(np.float32)
    s = (m - np.float32(1.0)) / (m + np.float32(1.0))
    z = s * s
    p = np.full_like(z, _COEFFS[-1])
    for c in _COEFFS[-2::-1]:
        p = p * z
        p = p + c
    r = (p * s + e) * np.float32(1.0 / 32.0)
    return np.where(d < 0, -r, r)


def features(trace: np.ndarray, w: Dict) -> Dict[str, np.ndarray]:
    """Model inputs of every instruction of ``trace``; ``w`` holds the
    configuration's widths (``num_regs``, ``fp_opcodes``, ``n_buckets``,
    ``n_queue``, ``n_mem``)."""
    n = len(trace)
    rows = np.arange(n)
    opcode = trace["opcode"].astype(np.int32)
    regbits = np.zeros((n, w["num_regs"]), np.float32)
    for col in ("src1", "src2", "dst"):
        regbits[rows, trace[col].astype(np.int64)] = 1.0
    flags = np.stack([
        trace["is_branch"], trace["taken"], trace["is_mem"], trace["is_store"],
        np.isin(opcode, w["fp_opcodes"]),
    ], axis=1).astype(np.float32)

    # branch history: the j-th branch of a bucket sees that bucket's
    # previous n_q outcomes, most recent first
    brhist = np.zeros((n, w["n_queue"]), np.float32)
    br = np.nonzero(trace["is_branch"])[0]
    if len(br):
        bucket = (trace["pc"][br] >> 2) % w["n_buckets"]
        taken = np.where(trace["taken"][br], 1.0, -1.0).astype(np.float32)
        order = np.argsort(bucket, kind="stable")
        b, t = bucket[order], taken[order]
        pos = np.arange(len(br))
        head = np.ones(len(br), bool)
        head[1:] = b[1:] != b[:-1]
        start = np.maximum.accumulate(np.where(head, pos, 0))
        hist = np.zeros((len(br), w["n_queue"]), np.float32)
        for k in range(w["n_queue"]):
            src = pos - 1 - k
            ok = src >= start
            hist[ok, k] = t[src[ok]]
        brhist[br[order]] = hist

    # memory distance: slot k of access j is the delta to access j-1-k
    memdist = np.zeros((n, w["n_mem"]), np.float32)
    mem = np.nonzero(trace["is_mem"])[0]
    addr = trace["addr"][mem].astype(np.int64)
    for k in range(min(w["n_mem"], max(len(mem) - 1, 0))):
        d = (addr[k + 1:] - addr[: len(mem) - 1 - k]).astype(np.float64)
        memdist[mem[k + 1:], k] = signed_log(d)
    return {"opcode": opcode, "regbits": regbits, "flags": flags,
            "brhist": brhist, "memdist": memdist}


def windows(a: np.ndarray, window: int) -> np.ndarray:
    """Non-overlapping windows of ``window`` rows (the trailing partial
    window is dropped; a trace shorter than one window is one window)."""
    if len(a) < window:
        return a[None]
    nw = (len(a) - window) // window + 1
    return a[: nw * window].reshape((nw, window) + a.shape[1:])
