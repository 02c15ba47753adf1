"""Pluggable device-side metric accumulators for the streaming engine.

The engine's jitted step used to hard-code one carry dict (CPI fetch sum,
branch-mispredict count, L1D-miss count, trailing exec latency).  This
module replaces that with a registry of ``MetricSpec``s: each metric
declares its own device-side accumulator — an ``init`` pytree, an
``update`` that folds one batch into it *inside* the jitted step, and a
host-side ``finalize`` — and the engine composes every requested spec into
the single compiled executable.  New metrics (phase curves, per-opcode
CPI, cache-level histograms, ...) are plug-in code, not engine surgery:

    from repro.engine.metrics import MetricSpec, register_metric

    DRAM_HITS = MetricSpec(
        name="dram_hits",
        init=lambda: jnp.zeros((), jnp.int32),
        update=lambda c, ctx: c + ctx.psum(
            ((ctx.dlevel == NUM_DLEVELS - 1) & ctx.is_mem)
            .sum(dtype=jnp.int32)),
        finalize=lambda c, n: {"dram_hits": float(c)},
    )
    register_metric(DRAM_HITS)
    engine = StreamingEngine(params, cfg, EngineConfig(
        metrics=("cpi", "dram_hits")))

Specs run on device, under jit, and — whatever ``ExecutionPlan`` the
engine resolved — inside ``shard_map``; ``StepContext.psum``/``pmax`` are
the cross-shard reducers (identity on a single-device plan), so a spec
written against the context works unchanged on a mesh.  ``ctx.batch``
exposes only the columns the engine ships (feature INPUT_KEYS, ``valid``,
``is_branch``, ``is_mem``) — a spec needing other trace columns must
drive the step with ``stream_batches(extra=...)`` (see tests/test_api.py
for a worked example).  The built-in specs reproduce the legacy carry's
values bit-for-bit (enforced by ``tests/test_api.py``).

**Windowed (phase-curve) metrics.**  A spec may declare a fixed
``(num_chunks,)`` carry and scatter per-window contributions into trace
phases with ``ctx.windowed_sum`` — the engine threads the global window
grid (``ctx.win_index`` / ``ctx.num_windows``) through the carry, so
Fig. 11-style phase curves accumulate **on device** under every plan (no
``collect=True`` round-trips) and cross shards through ``psum`` like any
other carry.  ``windowed_spec`` builds one; ``cpi_phase`` / ``l1d_phase``
are registered examples.  Their finalized value is a ``(num_chunks,)``
ndarray in ``SimulationResult.metrics``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..uarch.isa import DLEVEL_L2, NUM_DLEVELS

__all__ = [
    "StepContext",
    "MetricSpec",
    "METRIC_REGISTRY",
    "DEFAULT_METRICS",
    "DEFAULT_PHASE_CHUNKS",
    "register_metric",
    "resolve_metrics",
    "windowed_spec",
    "CPI",
    "BRANCH_MPKI",
    "L1D_MPKI",
    "DLEVEL_HIST",
    "CPI_PHASE",
    "L1D_PHASE",
    "detailed_input_mpki",
]


@dataclasses.dataclass(frozen=True)
class StepContext:
    """Everything a metric's ``update`` may read, for one (B, W) batch.

    All arrays are flattened to ``(B * W,)`` device arrays and live inside
    the jitted step (under ``shard_map`` they are the *local* shard).
    ``is_branch``/``is_mem`` are already masked to valid positions; the raw
    batch (feature columns, ``valid``, unmasked flags, ...) is in ``batch``.
    """

    valid: Any          # float32 validity mask (0.0 on padding)
    on: Any             # bool, valid > 0
    is_branch: Any      # bool, trace is_branch & on
    is_mem: Any         # bool, trace is_mem & on
    fetch_lat: Any      # float32, clamped >= 0
    exec_lat: Any       # float32, clamped >= 0
    mispred_prob: Any   # float32 sigmoid(mispred_logit)
    dlevel: Any         # int32 argmax(dlevel_logits)
    gidx: Any           # float32 global position key within the batch grid
    last_key: Any       # scalar: key of the globally-last valid position
                        # in this batch (-1.0 when the batch is all padding)
    psum: Callable[[Any], Any]   # cross-shard sum (identity off-mesh)
    pmax: Callable[[Any], Any]   # cross-shard max (identity off-mesh)
    sharded: bool
    batch: Dict[str, Any]
    # --- window grid (threaded through the engine's reserved carry) ---
    window: int = 0      # effective window length W (static)
    win_index: Any = None   # (B_local,) int32 TRACE-global window index of
                            # each local row (>= num_windows on padding rows)
    num_windows: Any = None  # int32 scalar: real windows in the whole trace

    # tao: hot
    def at_last(self, x) -> Any:
        """Value of ``x`` at the globally-last valid position of the batch
        (meaningful only when ``last_key >= 0``)."""
        if self.sharded:
            # the winning position lives on exactly one shard
            return self.psum(
                jnp.where(self.gidx == self.last_key, x, 0.0).sum(dtype=jnp.float32)
            )
        return x[jnp.argmax(jnp.where(self.on, self.gidx, -1.0)).astype(jnp.int32)]

    def per_window(self, x) -> Any:
        """Reshape a flattened ``(B_local*W,)`` array to local windows
        ``(B_local, W)``."""
        return x.reshape(-1, self.window)

    def chunk_of(self, num_chunks: int) -> Any:
        """Each local window's phase-chunk bucket in ``[0, num_chunks)``:
        the trace's window grid divided into ``num_chunks`` contiguous
        phases.  Padding windows clamp into the last bucket — harmless as
        long as their contribution is masked (``ctx.valid`` is 0 there).

        The index math is int32, so ``num_windows * num_chunks`` must fit
        in int32 — the engine enforces it per trace for any spec that
        declares ``MetricSpec.num_chunks`` (``windowed_spec`` does).
        """
        b = (self.win_index * num_chunks) // jnp.maximum(self.num_windows, 1)
        return jnp.clip(b, 0, num_chunks - 1)

    # tao: hot
    def windowed_sum(self, values, num_chunks: int) -> Any:
        """Scatter already-masked per-position ``values`` (``(B_local*W,)``;
        multiply by ``ctx.valid`` / ``ctx.on`` first) into a
        ``(num_chunks,)`` phase accumulator, summed across shards.  The
        carry stays a fixed shape no matter the trace length, so phase
        curves ride the same one-compile-per-geometry executable."""
        per_win = self.per_window(values).sum(axis=1)
        seg = jax.ops.segment_sum(
            per_win, self.chunk_of(num_chunks), num_segments=num_chunks
        )
        return self.psum(seg)


@dataclasses.dataclass(frozen=True)
class MetricSpec:
    """One device-side metric accumulator.

    ``init``     () -> device carry pytree (zeros)
    ``update``   (carry, StepContext) -> carry; traced into the jitted step
                 once per batch.  Cross-shard reductions must go through
                 ``ctx.psum``/``ctx.pmax``/``ctx.at_last``.
    ``finalize`` (host carry pytree, num_instructions) -> {metric: value};
                 runs on host after the single end-of-trace sync, and may
                 emit several named result metrics.  Values are floats for
                 scalars or ndarrays for curves (windowed specs emit their
                 ``(num_chunks,)`` phase curve).
    """

    name: str
    init: Callable[[], Any]
    update: Callable[[Any, "StepContext"], Any]
    finalize: Callable[[Any, int], Dict[str, Any]]
    # windowed (phase-curve) specs declare their carry length here so the
    # engine can enforce the int32 chunk-index envelope
    # (num_windows * num_chunks < 2^31) before streaming a trace
    num_chunks: Optional[int] = None


# ---------------------------------------------------------------------------
# Built-in specs (bit-for-bit the legacy carry)
# ---------------------------------------------------------------------------


def _cpi_init():
    # fetch_sum carries the only float rounding; the instruction count is
    # computed host-side from the window grid.
    return {
        "fetch_sum": jnp.zeros((), jnp.float32),
        "last_exec": jnp.zeros((), jnp.float32),
    }


# tao: hot
def _cpi_update(carry, ctx: StepContext):
    part = ctx.psum((ctx.fetch_lat * ctx.valid).sum(dtype=jnp.float32))
    return {
        "fetch_sum": carry["fetch_sum"] + part,
        # retire-clock formulation: total cycles end at the last valid
        # instruction's exec latency, so track it across batches
        "last_exec": jnp.where(
            ctx.last_key >= 0, ctx.at_last(ctx.exec_lat), carry["last_exec"]
        ),
    }


# tao: cold
def _cpi_finalize(carry, n: int) -> Dict[str, float]:
    total = float(carry["fetch_sum"] + carry["last_exec"])
    return {"cpi": total / max(n, 1), "total_cycles": total}


CPI = MetricSpec("cpi", _cpi_init, _cpi_update, _cpi_finalize)


def _int_count_init():
    # exact int32 counts (good to 2^31 instructions per trace)
    return jnp.zeros((), jnp.int32)


# tao: hot
def _branch_update(carry, ctx: StepContext):
    return carry + ctx.psum(
        ((ctx.mispred_prob > 0.5) & ctx.is_branch).sum(dtype=jnp.int32)
    )


# tao: cold
def _branch_finalize(carry, n: int) -> Dict[str, float]:
    return {"branch_mpki": 1000.0 * float(carry) / max(n, 1)}


BRANCH_MPKI = MetricSpec("branch_mpki", _int_count_init, _branch_update, _branch_finalize)


# tao: hot
def _l1d_update(carry, ctx: StepContext):
    return carry + ctx.psum(
        ((ctx.dlevel >= DLEVEL_L2) & ctx.is_mem).sum(dtype=jnp.int32)
    )


# tao: cold
def _l1d_finalize(carry, n: int) -> Dict[str, float]:
    return {"l1d_mpki": 1000.0 * float(carry) / max(n, 1)}


L1D_MPKI = MetricSpec("l1d_mpki", _int_count_init, _l1d_update, _l1d_finalize)


# A registered non-default plug-in: predicted data-access-level histogram
# over memory ops (cache-level composition, Fig. 11-style breakdowns).
def _dlevel_hist_init():
    return jnp.zeros((NUM_DLEVELS,), jnp.int32)


# tao: hot
def _dlevel_hist_update(carry, ctx: StepContext):
    onehot = jax.nn.one_hot(ctx.dlevel, NUM_DLEVELS, dtype=jnp.int32)
    return carry + ctx.psum(
        (onehot * ctx.is_mem[:, None].astype(jnp.int32)).sum(axis=0)
    )


_DLEVEL_NAMES = ("none", "l1", "l2", "dram")


# tao: cold
def _dlevel_hist_finalize(carry, n: int) -> Dict[str, float]:
    return {
        f"dlevel_{_DLEVEL_NAMES[i]}": float(carry[i]) for i in range(NUM_DLEVELS)
    }


DLEVEL_HIST = MetricSpec(
    "dlevel_hist", _dlevel_hist_init, _dlevel_hist_update, _dlevel_hist_finalize
)


# ---------------------------------------------------------------------------
# Windowed (phase-curve) specs: a declared (num_chunks,) device carry
# ---------------------------------------------------------------------------

# Fig. 11's curves resolve fine at this granularity; authors pick their own
DEFAULT_PHASE_CHUNKS = 32


def windowed_spec(
    name: str,
    value: Callable[["StepContext"], Any],
    *,
    num_chunks: int = DEFAULT_PHASE_CHUNKS,
    count: Optional[Callable[["StepContext"], Any]] = None,
) -> MetricSpec:
    """A phase-curve MetricSpec: mean of ``value(ctx)`` per trace phase.

    ``value`` returns per-position contributions (``(B_local*W,)``, valid
    positions only are counted — the factory masks with ``ctx.valid``).
    ``count`` picks the denominator population per position (a bool mask;
    default all valid instructions) — e.g. ``count=lambda ctx:
    ctx.is_mem`` makes the curve a rate over memory ops rather than over
    all instructions.  The carry is ``{"sum": (num_chunks,) f32,
    "count": (num_chunks,) i32}`` — fixed shape, device-resident,
    ``psum``-combined across shards — and ``finalize`` emits ``{name:
    (num_chunks,) float32 ndarray}`` (phases with an empty population
    divide by a clamped count of 1, i.e. report 0).  Counts are exact
    int32 under every plan; sums are float32 partial sums (same
    accumulation discipline as the built-in ``cpi`` spec).
    """
    if num_chunks < 1:
        raise ValueError(f"num_chunks must be >= 1, got {num_chunks}")

    def init():
        return {
            "sum": jnp.zeros((num_chunks,), jnp.float32),
            "count": jnp.zeros((num_chunks,), jnp.int32),
        }

    # tao: hot
    def update(carry, ctx: "StepContext"):
        vals = value(ctx).astype(jnp.float32) * ctx.valid
        pop = ctx.on if count is None else count(ctx)
        return {
            "sum": carry["sum"] + ctx.windowed_sum(vals, num_chunks),
            "count": carry["count"]
            + ctx.windowed_sum(pop.astype(jnp.int32), num_chunks),
        }

    # tao: cold
    def finalize(carry, n: int) -> Dict[str, Any]:
        cnt = np.asarray(carry["count"], dtype=np.int64)
        curve = np.asarray(carry["sum"], dtype=np.float32) / np.maximum(cnt, 1)
        return {name: curve.astype(np.float32)}

    return MetricSpec(name, init, update, finalize, num_chunks=num_chunks)


# Fig. 11-style phase curves: per-phase CPI (mean fetch cycles per
# instruction) and per-phase L1D miss rate over memory ops (count=is_mem
# picks the denominator population).  Registered, not default — request
# them via EngineConfig.metrics / simulate(metrics=...).
CPI_PHASE = windowed_spec("cpi_phase", lambda ctx: ctx.fetch_lat)
L1D_PHASE = windowed_spec(
    "l1d_phase",
    lambda ctx: ((ctx.dlevel >= DLEVEL_L2) & ctx.is_mem).astype(jnp.float32),
    count=lambda ctx: ctx.is_mem,
)


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

METRIC_REGISTRY: Dict[str, MetricSpec] = {}

# the legacy carry's metric set — what EngineConfig requests by default
DEFAULT_METRICS: Tuple[str, ...] = ("cpi", "branch_mpki", "l1d_mpki")


def register_metric(spec: MetricSpec, *, overwrite: bool = False) -> MetricSpec:
    if not overwrite and spec.name in METRIC_REGISTRY:
        raise ValueError(
            f"metric {spec.name!r} already registered "
            f"(pass overwrite=True to replace it)"
        )
    METRIC_REGISTRY[spec.name] = spec
    return spec


for _spec in (CPI, BRANCH_MPKI, L1D_MPKI, DLEVEL_HIST, CPI_PHASE, L1D_PHASE):
    register_metric(_spec)


def resolve_metrics(
    metrics: Tuple[Union[str, MetricSpec], ...],
) -> Tuple[MetricSpec, ...]:
    """Names -> registry lookup; MetricSpec instances pass through."""
    specs = []
    seen = set()
    for m in metrics:
        spec = m
        if isinstance(m, str):
            spec = METRIC_REGISTRY.get(m)
            if spec is None:
                raise KeyError(
                    f"unknown metric {m!r}; registered: "
                    f"{sorted(METRIC_REGISTRY)} (register_metric() adds more)"
                )
        elif not isinstance(m, MetricSpec):
            raise TypeError(f"metrics entries must be str or MetricSpec, got {m!r}")
        if spec.name in seen:
            raise ValueError(f"duplicate metric {spec.name!r}")
        seen.add(spec.name)
        specs.append(spec)
    if not specs:
        raise ValueError("at least one metric is required")
    return tuple(specs)


def detailed_input_mpki(rows: np.ndarray) -> Dict[str, float]:
    """Branch and L1D MPKI read from a detailed trace's rows (``mispred``;
    ``is_mem`` with ``dlevel`` at L2 or beyond).  A model that takes these
    events as inputs (SimNet) predicts neither: they are reported as what
    the detailed simulator saw, under names that say so."""
    n = max(len(rows), 1)
    l1d = rows["is_mem"] & (rows["dlevel"] >= DLEVEL_L2)
    return {"detailed_branch_mpki": 1000.0 * int(np.count_nonzero(rows["mispred"])) / n,
            "detailed_l1d_mpki": 1000.0 * int(np.count_nonzero(l1d)) / n}
