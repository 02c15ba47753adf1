"""DL-based simulation (inference) driver.

Streams a functional trace through a trained Tao model and aggregates the
predicted performance metrics:

  CPI          = (sum of predicted fetch latencies + final exec latency) / N
                 (retire-clock formulation of §4.2)
  branch MPKI  = predicted mispredictions per 1000 instructions
  L1D MPKI     = predicted accesses with level >= L2 per 1000 instructions
  phase curves = per-chunk averages (Fig. 11)

`simulate_trace` is a DEPRECATED compatibility wrapper over the streaming
engine (`repro.engine`) — new code should go through the `repro.api`
facade (`TrainedModel.simulate` / `Session.sweep`).  The original
host-side batch loop survives as `simulate_trace_legacy` — it is the
executable specification the engine is tested against, and the baseline
`benchmarks/bench_timing.py` measures the engine's speedup over.
"""
from __future__ import annotations

import time
import warnings
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..engine.runner import SimulationResult, simulate_trace_engine
from ..uarch.isa import DLEVEL_L2
from .dataset import stream_batches
from .features import FeatureSet, extract_features_reference
from .model import TaoConfig, tao_forward

__all__ = [
    "SimulationResult",
    "simulate_trace",
    "simulate_trace_legacy",
    "phase_curves",
]


def simulate_trace(
    params: Dict,
    func_trace: np.ndarray,
    cfg: TaoConfig,
    batch_size: int = 64,
    features: Optional[FeatureSet] = None,
    collect: bool = True,
    feature_backend: str = "numpy",
) -> SimulationResult:
    """Deprecated engine-backed simulation — use
    ``repro.api.TrainedModel.simulate`` (same engine, same results).
    `collect=False` keeps all metrics on device (fastest; per-instruction
    arrays are then not collected).  `feature_backend="fused"` runs §4.2
    feature extraction on the device, one program per batch
    (docs/engine.md)."""
    warnings.warn(
        "repro.core.simulate_trace is deprecated; use repro.api: "
        "TrainedModel(params, cfg).simulate(trace) or Session.sweep(...)",
        DeprecationWarning,
        stacklevel=2,
    )
    return simulate_trace_engine(
        params,
        func_trace,
        cfg,
        batch_size=batch_size,
        features=features,
        collect=collect,
        feature_backend=feature_backend,
    )


def simulate_trace_legacy(
    params: Dict,
    func_trace: np.ndarray,
    cfg: TaoConfig,
    batch_size: int = 64,
    features: Optional[FeatureSet] = None,
) -> SimulationResult:
    """Pre-engine host batch loop (reference implementation).

    Kept numerically verbatim apart from one fix: the branch/memory masks
    are taken with a single length-safe slice (the old double-slice
    under-filled the masks when the window grid overran the trace).  Uses
    the reference (interpreter-loop) feature extractor so it stays a
    faithful pre-refactor baseline end to end.  The windows now come from
    ``stream_batches`` over zero-copy views (``pad=False`` reproduces the
    old ragged batch slicing exactly) instead of a ``build_windows``
    materialization, so this labeling-side path no longer makes a full
    window copy of the trace — identical batch contents, O(batch) memory.
    """
    t0 = time.perf_counter()
    fs = features if features is not None else extract_features_reference(
        func_trace, cfg.features, with_labels=False
    )

    fwd = jax.jit(lambda p, b: tao_forward(p, b, cfg))

    fetch, execl, misp, dlev = [], [], [], []
    for batch in stream_batches(
        fs, cfg.window, batch_size, stride=cfg.window, pad=False
    ):
        batch.pop("valid")  # the legacy loop never padded: batches are ragged
        out = fwd(params, batch)
        fetch.append(np.asarray(out["fetch_lat"], np.float32))
        execl.append(np.asarray(out["exec_lat"], np.float32))
        misp.append(np.asarray(jax.nn.sigmoid(out["mispred_logit"]), np.float32))
        dlev.append(np.asarray(jnp.argmax(out["dlevel_logits"], -1), np.int32))

    fetch = np.maximum(np.concatenate(fetch).reshape(-1), 0.0)
    execl = np.maximum(np.concatenate(execl).reshape(-1), 0.0)
    misp = np.concatenate(misp).reshape(-1)
    dlev = np.concatenate(dlev).reshape(-1)
    n = len(fetch)

    # Masks from the trace itself (branch/memory heads only count where
    # valid).  The window grid covers the first n trace positions, so one
    # length-safe slice is all that is needed.
    covered = min(n, len(func_trace))
    is_branch = np.zeros(n, bool)
    is_mem = np.zeros(n, bool)
    is_branch[:covered] = func_trace["is_branch"][:covered]
    is_mem[:covered] = func_trace["is_mem"][:covered]

    total = float(fetch.sum() + (execl[-1] if n else 0.0))
    mispred_count = float((misp > 0.5)[is_branch].sum())
    l1d_miss_count = float((dlev >= DLEVEL_L2)[is_mem].sum())
    secs = time.perf_counter() - t0
    return SimulationResult(
        cpi=total / max(n, 1),
        total_cycles=total,
        branch_mpki=1000.0 * mispred_count / max(n, 1),
        l1d_mpki=1000.0 * l1d_miss_count / max(n, 1),
        num_instructions=n,
        seconds=secs,
        mips=n / 1e6 / secs,
        fetch_lat=fetch,
        exec_lat=execl,
        mispred_prob=misp,
        dlevel=dlev,
    )


def phase_curves(
    result: SimulationResult, chunk: int = 10_000
) -> Dict[str, np.ndarray]:
    """Per-chunk CPI / branch MPKI / L1D MPKI curves (Fig. 11)."""
    if "fetch_lat" not in result.available_metrics:
        raise ValueError(
            "phase_curves needs per-instruction predictions: simulate with "
            "collect=True (EngineConfig.collect)"
        )
    n = result.num_instructions
    m = n // chunk
    cpi = np.zeros(m)
    br = np.zeros(m)
    l1 = np.zeros(m)
    for i in range(m):
        s = slice(i * chunk, (i + 1) * chunk)
        cpi[i] = result.fetch_lat[s].mean()
        br[i] = 1000.0 * (result.mispred_prob[s] > 0.5).mean()
        l1[i] = 1000.0 * (result.dlevel[s] >= DLEVEL_L2).mean()
    return {"cpi": cpi, "branch_mpki": br, "l1d_mpki": l1}
