"""Public wrappers for the fused feature-extraction megakernel.

The contract mirrors ``kernels/features/ops`` — and is enforced by
``tests/test_fused.py`` on the CPU backend: the fused pipeline is
**bit-identical** to both the staged Pallas backend and the NumPy
specification (on the TPU, see docs/kernels.md "Exactness").  That falls
out of three invariants:

  * regbits/flags/brhist are exact integer/bool -> {0.0, 1.0, ±1.0} values —
    any compute path produces the same bits;
  * memory-distance deltas leave the kernel RAW (exact int32 subtraction,
    correctly-rounded cast) and the signed-log compression runs EAGERLY via
    ``signed_log_device`` — never inside a compiled program, where XLA's fma
    contraction of ``a*b + c`` would diverge in the last ulp;
  * the scan state threads across calls exactly (float copies and int32
    values), so batch-granular extraction equals one monolithic scan.

``FusedExtractor`` is the streaming driver the engine's ``"fused"`` backend
uses: raw int32/bool columns ship to the device once (~30 B/instr — the
same payload as the staged backend), then each ``next_batch`` slices one
batch worth of columns device-side, runs ONE megakernel launch, applies the
eager signed-log, and hands the model-input dict straight to the jitted
step.  Features exist only at batch granularity — no O(trace) FeatureSet in
HBM (see docs/kernels.md for the bandwidth accounting).
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...compat import on_tpu
from ...core.features import FeatureConfig
from ...uarch.isa import NUM_REGS, Op
from ..features.kernel import lanes
from ..features.ops import (
    DEFAULT_CHUNK,
    chunked_column,
    kernel_chunk,
    signed_log_device,
)
from .kernel import VCOLS, fused_feature_pallas

__all__ = [
    "FusedExtractor",
    "fused_feature_columns",
    "init_fused_state",
]

# opcodes whose instructions set the is_fp flag (static in the kernel)
_FP_OPS = (int(Op.FALU), int(Op.FMUL), int(Op.FDIV))

# the raw trace columns a fused pass consumes, in argument order
_COLUMN_KEYS = ("bucket", "addr") + VCOLS


def init_fused_state(cfg: FeatureConfig) -> Dict[str, jnp.ndarray]:
    """The scan carry threaded across megakernel calls, in the kernel's
    lane-padded layout: the (N_b, lanes(N_q)) branch-outcome table, and the
    address queue as a (2, lanes(N_m)) int32 block — row 0 the addresses,
    most recent first, row 1 which slots are filled.  Lanes past N_q / N_m
    are padding that never reaches a feature."""
    return {
        "table": jnp.zeros((cfg.n_buckets, lanes(cfg.n_queue)), jnp.float32),
        "queue": jnp.zeros((2, lanes(cfg.n_mem)), jnp.int32),
    }


@functools.partial(
    jax.jit, static_argnames=("n_queue", "n_mem", "n_flags", "chunk", "interpret")
)
def _fused_padded(cols, table, queue, *, n_queue, n_mem, n_flags, chunk, interpret):
    """``cols``: the ``_COLUMN_KEYS`` columns, each (n,)."""
    n = cols["bucket"].shape[0]
    outcome = jnp.where(
        cols["is_branch"],
        jnp.where(cols["taken"], jnp.float32(1.0), jnp.float32(-1.0)),
        jnp.float32(0.0),
    )
    per_instr = jnp.stack([cols[k].astype(jnp.int32) for k in VCOLS], axis=1)
    nc = max(1, -(-n // chunk))
    regbits, flags, brhist, memdist, table, queue = fused_feature_pallas(
        chunked_column(cols["bucket"].astype(jnp.int32), chunk),
        chunked_column(cols["addr"].astype(jnp.int32), chunk),
        chunked_column(outcome, chunk),
        chunked_column(cols["is_mem"].astype(jnp.int32), chunk),
        jnp.pad(per_instr, ((0, nc * chunk - n), (0, 0))),
        table,
        queue,
        n_queue=n_queue,
        n_mem=n_mem,
        n_flags=n_flags,
        num_regs=NUM_REGS,
        fp_ops=_FP_OPS,
        interpret=interpret,
    )
    return regbits[:n], flags[:n], brhist[:n], memdist[:n], table, queue


# tao: hot
def fused_feature_columns(
    cols: Dict,
    state: Dict[str, jnp.ndarray],
    cfg: FeatureConfig,
    *,
    chunk: int = DEFAULT_CHUNK,
    interpret: Optional[bool] = None,
) -> Tuple[Dict[str, jnp.ndarray], Dict[str, jnp.ndarray]]:
    """One fused device pass over (a slice of) the raw trace columns.

    Returns ``(features, new_state)`` where ``features`` holds the model
    inputs (``opcode``/``regbits``/``flags``/``brhist``/``memdist``) for
    exactly these positions and ``new_state`` is the scan carry to thread
    into the next slice.  Bit-identical to running the staged extraction
    over the concatenated slices.
    """
    if interpret is None:
        interpret = not on_tpu()
    regbits, flags, brhist, raw, table, queue = _fused_padded(
        {k: jnp.asarray(cols[k]) for k in _COLUMN_KEYS},
        state["table"],
        state["queue"],
        n_queue=cfg.n_queue,
        n_mem=cfg.n_mem,
        n_flags=cfg.flags_dim,
        chunk=kernel_chunk(chunk),
        interpret=interpret,
    )
    memdist = signed_log_device(raw)  # eager: keeps NumPy bit-equality
    feats = {
        "opcode": jnp.asarray(cols["opcode"], jnp.int32),
        "regbits": regbits,
        "flags": flags,
        "brhist": brhist,
        "memdist": memdist,
    }
    return feats, {"table": table, "queue": queue}


class FusedExtractor:
    """Streams fixed-size feature batches out of device-resident raw trace
    columns, carrying the scan state across batches.

    ``cols`` is the host dict from ``kernels.features.ops.trace_columns``
    (already validated against the int32-exact address window); it ships to
    the device ONCE here, zero-padded to ``pad_to`` positions so every
    ``next_batch(m)`` slice is uniform (pad rows are non-branch/non-mem and
    leave the carry untouched).  Each call runs one megakernel launch plus
    the eager signed-log and returns the model-input dict for the next
    ``m`` positions, including the sliced ``is_branch``/``is_mem`` bool
    columns the engine's step masks with.
    """

    # one-time host->device column upload, not the batch loop
    # tao: cold
    def __init__(
        self,
        cols: Dict[str, np.ndarray],
        cfg: FeatureConfig,
        *,
        chunk: int = DEFAULT_CHUNK,
        pad_to: Optional[int] = None,
        interpret: Optional[bool] = None,
    ):
        n = len(cols["bucket"])
        pad_to = n if pad_to is None else pad_to
        if pad_to < n:
            raise ValueError(f"pad_to ({pad_to}) < column length ({n})")
        self._cols: Dict[str, jnp.ndarray] = {}
        for k in _COLUMN_KEYS:
            a = jnp.asarray(cols[k])
            if pad_to > n:
                a = jnp.pad(a, (0, pad_to - n))
            self._cols[k] = a
        self._cfg = cfg
        self._chunk = chunk
        self._interpret = interpret
        self._pos = 0
        self._limit = pad_to
        self.state = init_fused_state(cfg)

    # tao: hot
    def next_batch(self, m: int) -> Dict[str, jnp.ndarray]:
        lo = self._pos
        if lo + m > self._limit:
            raise ValueError(
                f"next_batch({m}) past the padded column end "
                f"({lo} + {m} > {self._limit})"
            )
        self._pos = lo + m
        sl = {k: v[lo : lo + m] for k, v in self._cols.items()}
        feats, self.state = fused_feature_columns(
            sl,
            self.state,
            self._cfg,
            chunk=self._chunk,
            interpret=self._interpret,
        )
        feats["is_branch"] = sl["is_branch"]
        feats["is_mem"] = sl["is_mem"]
        return feats
