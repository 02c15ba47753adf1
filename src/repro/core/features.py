"""§4.2 Feature engineering from the µarch-agnostic functional trace.

Per-instruction features: opcode id (lookup-table embedding downstream),
register bitmap (src+dst, NUM_REGS wide), instruction flags.

Cross-instruction features:
  * branch-history hash table — N_b buckets × N_q outcomes keyed by
    (pc>>2) % N_b; a conditional branch's feature is its bucket's recent
    outcome queue (most-recent first; 0 for empty slots, ±1 for
    not-taken/taken).  Hash collisions deliberately mix histories of
    different branches, providing a lightweight global history (paper Fig 4).
  * memory access-distance queue — signed-log-compressed deltas between the
    current access address and the previous N_m accesses (paper Fig 3), a
    cheap stand-in for reuse/stack distance.

Defaults N_b=1024, N_q=32, N_m=64 are the paper's empirically chosen values
(§5.4); the benchmark harness sweeps them (Fig 12).

Two implementations of the cross-instruction features:

  * `extract_features` — vectorized.  Branch history is computed per-bucket
    with a grouped (sort-by-bucket) formulation; the memory-distance queue is
    a lag-k difference.  Both loop over the queue depth (N_q / N_m, small
    constants) instead of over the trace.
  * `extract_features_reference` — the original per-branch / per-access
    interpreter loops, kept as the executable specification; the test suite
    asserts exact equivalence between the two.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional

import numpy as np

from ..uarch.isa import NUM_REGS, Op

__all__ = [
    "FeatureConfig",
    "FeatureSet",
    "extract_features",
    "extract_features_reference",
    "num_extractions",
    "signed_log",
    "SIGNED_LOG_COEFFS",
    "SIGNED_LOG_SQRT2",
    "NUM_OPCODES",
]

NUM_OPCODES = len(Op)

# process-wide count of full feature-extraction passes (the O(trace)
# host pre-pass) — snapshot before/after a region to prove it was served
# from cache/store instead of recomputed (the cross-process reuse tests
# pin this to zero against a warm store)
_NUM_EXTRACTIONS = 0


def num_extractions() -> int:
    """How many times ``extract_features`` has run in this process."""
    return _NUM_EXTRACTIONS


@dataclasses.dataclass(frozen=True)
class FeatureConfig:
    n_buckets: int = 1024   # N_b
    n_queue: int = 32       # N_q
    n_mem: int = 64         # N_m

    @property
    def flags_dim(self) -> int:
        return 5  # is_branch, taken, is_mem, is_store, is_fp


@dataclasses.dataclass
class FeatureSet:
    """Model inputs (+ labels when built from an adjusted trace)."""

    opcode: np.ndarray      # (N,) int32
    regbits: np.ndarray     # (N, NUM_REGS) float32
    flags: np.ndarray       # (N, 5) float32
    brhist: np.ndarray      # (N, N_q) float32 in {-1, 0, +1}
    memdist: np.ndarray     # (N, N_m) float32 signed-log deltas
    labels: Optional[Dict[str, np.ndarray]] = None

    def __len__(self) -> int:
        return len(self.opcode)

    @property
    def digest(self) -> str:
        """Stable content digest (blake2b over every array, labels
        included) — the identity the sweep scheduler's dedup and the
        artifact store share, instead of object ids.  Cached on first use;
        treat the arrays as immutable once hashed."""
        d = getattr(self, "_digest", None)
        if d is None:
            from ..store.content import tree_digest

            d = tree_digest(
                {
                    "opcode": self.opcode,
                    "regbits": self.regbits,
                    "flags": self.flags,
                    "brhist": self.brhist,
                    "memdist": self.memdist,
                    "labels": self.labels,
                }
            )
            self._digest = d
        return d

    def slice(self, lo: int, hi: int) -> "FeatureSet":
        lab = None
        if self.labels is not None:
            lab = {k: v[lo:hi] for k, v in self.labels.items()}
        return FeatureSet(
            opcode=self.opcode[lo:hi],
            regbits=self.regbits[lo:hi],
            flags=self.flags[lo:hi],
            brhist=self.brhist[lo:hi],
            memdist=self.memdist[lo:hi],
            labels=lab,
        )


_FP_OPS = (int(Op.FALU), int(Op.FMUL), int(Op.FDIV))


def _per_instruction(trace: np.ndarray, opcode: np.ndarray):
    n = len(trace)
    regbits = np.zeros((n, NUM_REGS), dtype=np.float32)
    rows = np.arange(n)
    regbits[rows, trace["src1"].astype(np.int64)] = 1.0
    regbits[rows, trace["src2"].astype(np.int64)] = 1.0
    # dst included too (paper: both source and destination registers)
    regbits[rows, trace["dst"].astype(np.int64)] = 1.0

    is_fp = np.isin(opcode, _FP_OPS)
    flags = np.stack(
        [
            trace["is_branch"].astype(np.float32),
            trace["taken"].astype(np.float32),
            trace["is_mem"].astype(np.float32),
            trace["is_store"].astype(np.float32),
            is_fp.astype(np.float32),
        ],
        axis=1,
    )
    return regbits, flags


def _labels(trace: np.ndarray, with_labels: bool):
    if not (with_labels and "fetch_lat" in trace.dtype.names):
        return None
    return {
        "fetch_lat": trace["fetch_lat"].astype(np.float32),
        "exec_lat": trace["exec_lat"].astype(np.float32),
        "mispred": trace["mispred"].astype(np.float32),
        "dlevel": trace["dlevel"].astype(np.int32),
        "icache_miss": trace["icache_miss"].astype(np.float32),
        "tlb_miss": trace["tlb_miss"].astype(np.float32),
        "is_branch": trace["is_branch"].astype(np.float32),
        "is_mem": trace["is_mem"].astype(np.float32),
    }


# ---------------------------------------------------------------------------
# Deterministic signed-log compression.
#
# sign(d) * log2(1 + |d|) / 32 evaluated as a FIXED sequence of exactly
# rounded float32 operations: exponent/mantissa split by bit manipulation,
# then an atanh-series polynomial (Horner) for log2 of the mantissa.  Every
# step is an individually rounded IEEE-754 float32 op, so NumPy and the jax
# twin (``repro.kernels.features.ops.signed_log_device``) produce
# bit-identical results — the property the device feature backends'
# exact-equivalence tests rely on.  This function stays the spec.
#
# The decision on compiled evaluation lives here.  A plain jitted
# evaluation is NOT bit-identical: XLA contracts `a*b + c` into an fma,
# which rounds once instead of twice (`lax.optimization_barrier` does not
# stop it).  So the jax twin keeps this exact chain of ops and routes
# every product that feeds an add (`s * s`, each Horner `p * z`, `p * s`)
# through an integer identity the compiler cannot see:
# bitcast_f32(bitcast_i32(p) | zero), with `zero` an int32 argument,
# traced inside a compiled program.  That forces the product to round to
# float32 first, so the compiled twin equals NumPy bit for bit on the CPU
# and runs inside the fused extraction program; on the TPU it stays within
# 1 ulp, as the eager evaluation did (docs/kernels.md "Exactness").  Lower precision, a table
# or skipping the compression would be a different result.  Max relative
# error vs true log2 is ~6e-8 (≈1 ulp).
# ---------------------------------------------------------------------------

# 2/ln2 * s^(2k) atanh-series coefficients: log2(m) = (2/ln2)·atanh(s) with
# s = (m-1)/(m+1); degree 13 keeps the error ≈1 ulp over m ∈ [√2/2, √2].
SIGNED_LOG_COEFFS = tuple(
    np.float32(2.0 / math.log(2.0) / k) for k in (1, 3, 5, 7, 9, 11, 13)
)
SIGNED_LOG_SQRT2 = np.float32(math.sqrt(2.0))


# tao: bitwise
def signed_log(d: np.ndarray) -> np.ndarray:
    """Signed-log-compress deltas to float32, bit-reproducibly (see above)."""
    d = np.asarray(d).astype(np.float32)
    a = np.abs(d)
    x = np.float32(1.0) + a
    bits = x.view(np.int32)
    e = ((bits >> 23) & np.int32(0xFF)) - np.int32(127)
    m = ((bits & np.int32(0x007FFFFF)) | np.int32(0x3F800000)).view(np.float32)
    big = m > SIGNED_LOG_SQRT2
    m = np.where(big, m * np.float32(0.5), m)
    e = (e + big).astype(np.float32)
    s = (m - np.float32(1.0)) / (m + np.float32(1.0))
    z = s * s
    p = np.full_like(z, SIGNED_LOG_COEFFS[-1])
    for c in SIGNED_LOG_COEFFS[-2::-1]:
        p = p * z
        p = p + c
    r = p * s
    r = r + e
    r = r * np.float32(1.0 / 32.0)
    return np.where(d < 0, -r, r)


_signed_log = signed_log


def _branch_history(trace: np.ndarray, cfg: FeatureConfig) -> np.ndarray:
    """Grouped (per-bucket) formulation of the branch-history hash table.

    The j-th branch mapping to bucket b sees that bucket's previous N_q
    outcomes, most-recent first.  A stable sort by bucket makes every bucket's
    branches contiguous, turning the lookup into lag-k gathers: only the queue
    depth (N_q) is a Python loop, each iteration vectorized over all branches.
    """
    n = len(trace)
    brhist = np.zeros((n, cfg.n_queue), dtype=np.float32)
    br_idx = np.nonzero(trace["is_branch"])[0]
    m = len(br_idx)
    if m == 0:
        return brhist
    bucket = ((trace["pc"][br_idx] >> 2) % cfg.n_buckets).astype(np.int64)
    taken = np.where(trace["taken"][br_idx], 1.0, -1.0).astype(np.float32)

    order = np.argsort(bucket, kind="stable")
    b_sorted = bucket[order]
    t_sorted = taken[order]
    pos = np.arange(m)
    # start index (in sorted order) of the group each branch belongs to
    is_head = np.empty(m, dtype=bool)
    is_head[0] = True
    is_head[1:] = b_sorted[1:] != b_sorted[:-1]
    group_start = np.maximum.accumulate(np.where(is_head, pos, 0))

    rows = np.zeros((m, cfg.n_queue), dtype=np.float32)
    for k in range(cfg.n_queue):
        src = pos - 1 - k
        valid = src >= group_start
        rows[valid, k] = t_sorted[src[valid]]
    brhist[br_idx[order]] = rows
    return brhist


def _memory_distance(trace: np.ndarray, cfg: FeatureConfig) -> np.ndarray:
    """Lag-k formulation of the access-distance queue: slot k of access j is
    the signed-log delta to access j-1-k.  Loops over N_m, not the trace."""
    n = len(trace)
    memdist = np.zeros((n, cfg.n_mem), dtype=np.float32)
    mem_idx = np.nonzero(trace["is_mem"])[0]
    m = len(mem_idx)
    if m < 2:
        return memdist
    addrs = trace["addr"][mem_idx].astype(np.int64)
    for k in range(min(cfg.n_mem, m - 1)):
        d = (addrs[k + 1 :] - addrs[: m - 1 - k]).astype(np.float64)
        memdist[mem_idx[k + 1 :], k] = _signed_log(d)
    return memdist


def extract_features(
    trace: np.ndarray, cfg: FeatureConfig = FeatureConfig(), with_labels: bool = True
) -> FeatureSet:
    """`trace` is either an adjusted trace (ADJ_DTYPE, labels available) or a
    raw functional trace (FUNC_TRACE_DTYPE, inference path)."""
    global _NUM_EXTRACTIONS
    _NUM_EXTRACTIONS += 1
    opcode = trace["opcode"].astype(np.int32)
    regbits, flags = _per_instruction(trace, opcode)
    return FeatureSet(
        opcode=opcode,
        regbits=regbits,
        flags=flags,
        brhist=_branch_history(trace, cfg),
        memdist=_memory_distance(trace, cfg),
        labels=_labels(trace, with_labels),
    )


def extract_features_reference(
    trace: np.ndarray, cfg: FeatureConfig = FeatureConfig(), with_labels: bool = True
) -> FeatureSet:
    """Original interpreter-loop implementation (executable specification for
    `extract_features`; quadratic-free but O(trace) Python overhead)."""
    n = len(trace)
    opcode = trace["opcode"].astype(np.int32)
    regbits, flags = _per_instruction(trace, opcode)

    # ---- branch-history hash table (sequential over branches) ----------
    brhist = np.zeros((n, cfg.n_queue), dtype=np.float32)
    table = np.zeros((cfg.n_buckets, cfg.n_queue), dtype=np.float32)
    br_idx = np.nonzero(trace["is_branch"])[0]
    br_pc = (trace["pc"][br_idx] >> 2) % cfg.n_buckets
    br_taken = np.where(trace["taken"][br_idx], 1.0, -1.0).astype(np.float32)
    for j in range(len(br_idx)):
        b = br_pc[j]
        row = table[b]
        brhist[br_idx[j]] = row
        # push most-recent-first
        row[1:] = row[:-1]
        row[0] = br_taken[j]

    # ---- memory access-distance queue (sequential over mem ops) --------
    memdist = np.zeros((n, cfg.n_mem), dtype=np.float32)
    queue = np.zeros(cfg.n_mem, dtype=np.int64)
    filled = 0
    mem_idx = np.nonzero(trace["is_mem"])[0]
    addrs = trace["addr"][mem_idx].astype(np.int64)
    for j in range(len(mem_idx)):
        a = addrs[j]
        if filled:
            d = (a - queue[:filled]).astype(np.float64)
            memdist[mem_idx[j], :filled] = _signed_log(d)
        queue[1:] = queue[:-1]
        queue[0] = a
        if filled < cfg.n_mem:
            filled += 1

    return FeatureSet(
        opcode=opcode,
        regbits=regbits,
        flags=flags,
        brhist=brhist,
        memdist=memdist,
        labels=_labels(trace, with_labels),
    )
