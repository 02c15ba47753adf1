"""Public wrappers for the fused feature-extraction megakernel.

The contract mirrors ``kernels/features/ops`` — and is enforced by
``tests/test_fused.py`` on the CPU backend: the fused pipeline is
**bit-identical** to both the staged Pallas backend and the NumPy
specification (on the TPU, see docs/kernels.md "Exactness").  That falls
out of three invariants:

  * regbits/flags/brhist are exact integer/bool -> {0.0, 1.0, ±1.0} values —
    any compute path produces the same bits;
  * memory-distance deltas leave the kernel RAW (exact int32 subtraction,
    correctly-rounded cast) and the signed-log compression runs inside the
    same compiled program through ``signed_log_device`` with a traced zero:
    each product is rounded through an integer barrier, so XLA cannot
    contract ``a*b + c`` into an fma that would diverge in the last ulp;
  * the scan state threads across calls exactly (float copies and int32
    values), so batch-granular extraction equals one monolithic scan.

``FusedExtractor`` is the streaming driver the engine's ``"fused"`` backend
uses.  The raw int32/bool columns stay on the host; each ``next_batch``
packs one batch of them into a single fixed-shape int32 array (zero-padded
past the trace's end) and dispatches ONE compiled program,
``_fused_padded``: the megakernel, the signed-log, the per-position
``opcode``/``is_branch``/``is_mem``/``valid`` fields and the reshape to the
step's batch layout.  One host->device copy (40 B/instr) and one launch per
batch, one compile per batch shape.  Features exist only at batch
granularity — no O(trace) FeatureSet in HBM (see docs/kernels.md for the
bandwidth accounting).
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
    jax.jit,
    static_argnames=("shape", "n_queue", "n_mem", "n_flags", "chunk", "interpret"),
)
def _fused_padded(
    packed, table, queue, counts, *, shape, n_queue, n_mem, n_flags, chunk, interpret
):
    """The whole extraction of ``m`` positions, one program.

    ``packed``: (len(_COLUMN_KEYS), m) int32, the raw columns in
    ``_COLUMN_KEYS`` order; ``counts``: int32 (2,), the real positions
    among the ``m`` (the rest are padding, ``valid`` 0) and a zero for
    ``signed_log_device``'s rounding barriers.  Returns ``(batch, table,
    queue)``: every field of ``batch`` is reshaped to ``shape + (…)``, with
    ``prod(shape) == m``."""
    m = packed.shape[1]
    col = dict(zip(_COLUMN_KEYS, packed))
    is_branch = col["is_branch"] != 0
    is_mem = col["is_mem"] != 0
    outcome = jnp.where(
        is_branch,
        jnp.where(col["taken"] != 0, jnp.float32(1.0), jnp.float32(-1.0)),
        jnp.float32(0.0),
    )
    nc = max(1, -(-m // chunk))
    regbits, flags, brhist, raw, table, queue = fused_feature_pallas(
        chunked_column(col["bucket"], chunk),
        chunked_column(col["addr"], chunk),
        chunked_column(outcome, chunk),
        chunked_column(col["is_mem"], chunk),
        jnp.pad(packed[2:].T, ((0, nc * chunk - m), (0, 0))),  # the VCOLS rows
        table,
        queue,
        n_queue=n_queue,
        n_mem=n_mem,
        n_flags=n_flags,
        num_regs=NUM_REGS,
        fp_ops=_FP_OPS,
        interpret=interpret,
    )
    fields = {
        "opcode": col["opcode"],
        "regbits": regbits[:m],
        "flags": flags[:m],
        "brhist": brhist[:m],
        "memdist": signed_log_device(raw[:m], counts[1]),
        "is_branch": is_branch,
        "is_mem": is_mem,
        "valid": (jnp.arange(m) < counts[0]).astype(jnp.float32),
    }
    batch = {k: v.reshape(shape + v.shape[1:]) for k, v in fields.items()}
    return batch, table, queue


def _pack(cols: Dict[str, np.ndarray], lo: int, m: int) -> np.ndarray:
    """Positions ``[lo, lo + m)`` of the host columns as one
    (len(_COLUMN_KEYS), m) int32 array, zero past the columns' end (pad
    positions are non-branch, non-mem: the carry passes through them)."""
    k = max(0, min(m, len(cols["bucket"]) - lo))
    out = np.empty((len(_COLUMN_KEYS), m), np.int32)
    for j, key in enumerate(_COLUMN_KEYS):
        out[j, :k] = cols[key][lo : lo + k]
    out[:, k:] = 0
    return out


class FusedExtractor:
    """Streams fixed-size feature batches out of the raw host trace
    columns, carrying the scan state across batches.

    ``cols`` is the host dict from ``kernels.features.ops.trace_columns``
    (already validated against the int32-exact address window), read as
    if zero-padded to ``pad_to`` positions (pad rows are non-branch/non-mem
    and leave the carry untouched).  Each ``next_batch(m)`` ships one
    packed (len(_COLUMN_KEYS), m) int32 array and runs one compiled
    extraction program; it returns the model-input dict for the next ``m``
    positions, including the ``is_branch``/``is_mem`` bool columns the
    engine's step masks with and ``valid`` (0.0 on pad positions).
    ``state`` is the scan carry to start from (default
    ``init_fused_state(cfg)``); it is read, never written, so one zero
    carry can start any number of extractors.
    """

    def __init__(
        self,
        cols: Dict[str, np.ndarray],
        cfg: FeatureConfig,
        *,
        chunk: int = DEFAULT_CHUNK,
        pad_to: Optional[int] = None,
        interpret: Optional[bool] = None,
        state: Optional[Dict[str, jnp.ndarray]] = None,
    ):
        n = len(cols["bucket"])
        pad_to = n if pad_to is None else pad_to
        if pad_to < n:
            raise ValueError(f"pad_to ({pad_to}) < column length ({n})")
        self._cols = cols
        self._n = n
        self._static = dict(
            n_queue=cfg.n_queue,
            n_mem=cfg.n_mem,
            n_flags=cfg.flags_dim,
            chunk=kernel_chunk(chunk),
            interpret=not on_tpu() if interpret is None else interpret,
        )
        self._pos = 0
        self._limit = pad_to
        self.state = init_fused_state(cfg) if state is None else state

    # tao: hot
    def next_batch(
        self, m: int, shape: Optional[Tuple[int, ...]] = None
    ) -> Dict[str, jnp.ndarray]:
        """The next ``m`` positions; every field is shaped ``shape + (…)``
        (default ``(m,)``)."""
        lo = self._pos
        if lo + m > self._limit:
            raise ValueError(
                f"next_batch({m}) past the padded column end "
                f"({lo} + {m} > {self._limit})"
            )
        self._pos = lo + m
        real = max(0, min(m, self._n - lo))
        batch, table, queue = _fused_padded(
            _pack(self._cols, lo, m),
            self.state["table"],
            self.state["queue"],
            np.array([real, 0], np.int32),
            shape=(m,) if shape is None else tuple(shape),
            **self._static,
        )
        self.state = {"table": table, "queue": queue}
        return batch
