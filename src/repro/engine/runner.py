"""Device-resident streaming simulation engine.

The fast path behind §4.2 inference: a functional trace flows through

  vectorized features  ->  zero-copy window views  ->  fixed-shape padded
  batches (+ validity mask)  ->  one jitted forward/accumulate step  ->
  device-resident metric accumulators (``MetricSpec`` registry).

Design points (each measured by ``benchmarks/bench_timing.py``):

  * **Two geometries at most.**  Every full batch has shape
    (batch_size, W); a request's last batch is zero-padded and masked
    instead of retraced, on half the rows when at most half a batch of
    windows is left (``batch_rows``), so padding stays under half a
    batch per request.  The whole run — and every later trace with the
    same effective window — reuses the one or two executables.
  * **On-device accumulation.**  The step folds each batch into the carry
    pytrees declared by the requested ``MetricSpec``s (``engine.metrics``):
    CPI / branch-MPKI / L1D-MPKI by default, anything plug-in code
    registers otherwise.  The instruction count comes from the window grid
    on host, and per-instruction arrays are only transferred when
    ``EngineConfig.collect`` asks for them.  The all-zero part of a
    request's initial state (the specs' carries, the fused scan carry) is
    built once per engine and kept (``state_builds``); per request the
    trace's window count is the one new device value.
  * **Prefetch.**  The next batch's host->device transfer is enqueued before
    the current result is consumed, overlapping copy with compute.
  * **Partitioning.**  Every placement/wrapping/index-mapping decision is
    owned by an ``ExecutionPlan`` (``engine/plan.py``), resolved once per
    ``EngineConfig`` from its ``plan=`` or ``mesh=``: the single-device
    plan is a no-op wrapper, a sharded plan runs the step under
    ``shard_map`` with the batch dimension split over the plan's batch
    axes, and specs reduce across shards through
    ``StepContext.psum``/``pmax``.  The plan is part of the step-cache
    key, so the one-compile guarantee holds per (geometry, plan).
  * **Feature backends.**  ``feature_backend="numpy"`` extracts features
    on the host (the reference path); ``feature_backend="fused"`` replaces
    that pre-pass with one megakernel launch per batch
    (``kernels/fused/``), which produces the model inputs directly from
    the raw trace columns with the scan state carried across batches —
    features only ever exist at batch granularity, never as an O(trace)
    FeatureSet in HBM.  Bit-identical to the NumPy path on the CPU backend
    (docs/engine.md; for the TPU, docs/kernels.md); both backends share
    the step cache.
  * **Stacked design points.**  ``heads=K`` (K >= 2) takes a params tree
    whose every leaf carries a leading axis of K models of one shape (the
    §4.3 design-space sweep: per-design heads over one embedding).  The
    step maps the one-model body over params and carry with the batch
    shared, so each batch is extracted once and every head runs over it
    in one launch; ``simulate_heads`` returns one result per head.  K is part
    of the step-cache key; a one-model engine keeps its own step.
  * **Precision.**  ``precision="int8"`` swaps the step's forward for the
    W8A8 quantized twin (``core/quant.py``): per-channel int8 weights +
    dynamic per-row int8 activations with int32 accumulation.  The
    quantized tree is computed once per engine (or injected pre-quantized
    via ``qparams=`` — the ArtifactStore / registry path) and the choice
    is part of the step-cache key.

``repro.api.Session`` / ``TrainedModel.simulate`` are the supported entry
points; ``core.simulate.simulate_trace`` survives as a deprecation shim and
the original host-loop implementation as ``simulate_trace_legacy``, which
the test suite holds the engine to.
"""
from __future__ import annotations

import dataclasses
import functools
import queue
import threading
import time
from typing import Dict, Iterator, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from ..compat import Mesh, PartitionSpec as P
from ..core.dataset import INPUT_KEYS, num_windows, stream_batches
from ..core.features import FeatureSet, extract_features
from ..core.model import TaoConfig, tao_forward
from ..core.quant import quantize_tao_params, tao_forward_int8
from ..uarch.isa import NUM_REGS
from ..resilience.faults import fault_point
from ..spans import call_span, in_call, span
from .aot import abstract_like, compile_bytes_estimate
from .metrics import DEFAULT_METRICS, MetricSpec, StepContext, resolve_metrics
from .plan import ExecutionPlan

# NOTE: repro.kernels.fused.ops is imported lazily inside simulate(); a
# module-level import would close an import cycle (kernels.fused.ops ->
# repro.core package init -> core.simulate -> engine.runner) and crash any
# consumer whose first repro import is the ops module.

__all__ = [
    "EngineConfig",
    "FEATURE_BACKENDS",
    "PRECISIONS",
    "PER_INSTRUCTION_KEYS",
    "MetricNotCollectedError",
    "MetricNotComputedError",
    "SimulationResult",
    "StreamingEngine",
    "cache_stats",
    "clear_step_cache",
    "prefetch_to_device",
    "simulate_trace_engine",
]


# ---------------------------------------------------------------------------
# Host→device prefetch, shared by the simulation engine and the streaming
# training pipeline (core/transfer.py).
# ---------------------------------------------------------------------------

_PREFETCH_STOP = object()


def _threaded_prefetch(host_batches, put, depth: int) -> Iterator:
    q: "queue.Queue" = queue.Queue(maxsize=depth)
    stop = threading.Event()
    error: list = []

    def produce():
        try:
            for b in host_batches:
                with span("feed.put"):
                    dev = put(b)
                while not stop.is_set():
                    try:
                        q.put(dev, timeout=0.1)
                        break
                    except queue.Full:
                        continue
                if stop.is_set():
                    return
        except BaseException as e:  # re-raised in the consumer
            error.append(e)
        finally:
            while not stop.is_set():
                try:
                    q.put(_PREFETCH_STOP, timeout=0.1)
                    break
                except queue.Full:
                    continue

    producer = threading.Thread(
        target=in_call(produce), name="batch-prefetch", daemon=True
    )
    producer.start()
    try:
        while True:
            with span("feed.wait"):
                item = q.get()
            if item is _PREFETCH_STOP:
                break
            yield item
    finally:
        # normal exhaustion, consumer error, or an abandoned generator:
        # unpark the producer and drop prepared-but-unconsumed batches
        stop.set()
        while True:
            try:
                q.get_nowait()
            except queue.Empty:
                break
        producer.join()
        if error:
            raise error[0]


def prefetch_to_device(
    host_batches: Iterator,
    device_put=None,
    *,
    threaded: Optional[bool] = None,
    depth: int = 2,
) -> Iterator:
    """Double-buffered host→device prefetch over a batch iterator.

    Two modes, following the sweep scheduler's measured policy
    (``engine/scheduler.py``):

    * **inline** (CPU default): batch i+1's transfer is enqueued before
      batch i is yielded — copy overlaps compute with zero thread overhead.
      On a CPU-only backend a producer thread would contend with the
      consumer's own compute for the same cores.
    * **threaded** (accelerator default): a daemon producer thread pushes
      transfers into a bounded queue ``depth`` deep, so the *host-side*
      work of producing batch i+1 (window gather, padding) also overlaps
      device execution of batch i.

    ``depth`` only shapes the threaded queue; inline mode is inherently
    one-ahead (depth 1) — a deeper inline buffer would just hold more
    host batches alive without adding overlap, since the consumer and
    producer share one thread.

    Producer errors re-raise in the consumer; abandoning the generator
    (``close()`` / early break) stops the producer thread.
    """
    if depth < 1:
        raise ValueError(f"prefetch depth must be >= 1, got {depth}")
    put = device_put if device_put is not None else jax.device_put
    if threaded is None:
        threaded = jax.default_backend() != "cpu"
    if threaded:
        return _threaded_prefetch(host_batches, put, depth)

    def inline():
        # the consumer waits while the next host batch is made: spans
        # close before every yield
        it = iter(host_batches)
        cur = _PREFETCH_STOP
        while True:
            with span("feed.wait"):
                nxt = next(it, _PREFETCH_STOP)
            if nxt is _PREFETCH_STOP:
                break
            with span("feed.put"):
                nxt = put(nxt)
            if cur is not _PREFETCH_STOP:
                yield cur
            cur = nxt
        if cur is not _PREFETCH_STOP:
            yield cur

    return inline()


FEATURE_BACKENDS = ("numpy", "fused")

PRECISIONS = ("fp32", "int8")

# per-instruction prediction arrays the step can emit under collect=True
PER_INSTRUCTION_KEYS = ("fetch_lat", "exec_lat", "mispred_prob", "dlevel")

# SimulationResult instance attributes that would shadow a same-named
# metric (instance dict wins over __getattr__)
_RESERVED_RESULT_ATTRS = frozenset(
    ("num_instructions", "seconds", "mips", "metrics")
)

# reserved carry slot threading the trace's window grid (running window
# offset + total windows) through the step for windowed MetricSpecs
_GRID_KEY = "__grid__"


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    batch_size: int = 64
    collect: bool = False        # also return per-instruction predictions
    prefetch: bool = True        # overlap host->device copy with compute
    # Partitioning: pass a resolved ExecutionPlan, or just a mesh and the
    # engine resolves one (both None -> the single-device plan).
    mesh: Optional[Mesh] = None
    plan: Optional[ExecutionPlan] = None
    # "numpy": host NumPy pre-pass + per-batch host->device transfers.
    # "fused": one megakernel launch per batch (kernels/fused/) produces
    # the model inputs straight from the raw columns, scan state carried
    # across batches — no O(trace) feature materialization.  It matches
    # the NumPy path exactly on the CPU backend (docs/kernels.md says what
    # holds on the TPU) and raises ValueError on traces with addresses
    # outside |addr| < 2^30.
    feature_backend: str = "numpy"
    # "fp32": exact float path.  "int8": W8A8 quantized forward — per-
    # channel int8 weights + dynamic per-row int8 activations, int32
    # accumulation (core/quant.py; gated on accuracy parity by
    # bench_accuracy).
    precision: str = "fp32"
    # device-side accumulators composed into the jitted step: registry names
    # or MetricSpec instances (see engine.metrics / docs/api.md)
    metrics: Tuple[Union[str, MetricSpec], ...] = DEFAULT_METRICS


class MetricNotCollectedError(AttributeError):
    """A per-instruction array was requested but the engine kept metrics on
    device (``EngineConfig.collect=False``)."""


class MetricNotComputedError(AttributeError):
    """A scalar metric was requested whose ``MetricSpec`` was not part of
    the simulation's ``EngineConfig.metrics``."""


class SimulationResult:
    """Aggregated metrics of one simulated trace.

    Scalar metrics (whatever the run's ``MetricSpec``s finalized — ``cpi``,
    ``total_cycles``, ``branch_mpki``, ``l1d_mpki`` with the default set)
    are attributes and live in ``.metrics``; per-instruction prediction
    arrays (``fetch_lat``, ``exec_lat``, ``mispred_prob``, ``dlevel``) are
    attributes only when the run collected them.  ``available_metrics``
    lists everything present; accessing an uncollected array raises
    ``MetricNotCollectedError`` and a metric that was never computed raises
    ``MetricNotComputedError`` (both are ``AttributeError`` subclasses).
    """

    def __init__(
        self,
        num_instructions: int,
        seconds: float,
        mips: float,
        metrics: Optional[Dict[str, float]] = None,
        arrays: Optional[Dict[str, Optional[np.ndarray]]] = None,
        **legacy,
    ):
        self.num_instructions = num_instructions
        self.seconds = seconds
        self.mips = mips
        self.metrics: Dict[str, float] = dict(metrics or {})
        self._arrays: Dict[str, Optional[np.ndarray]] = (
            dict(arrays)
            if arrays is not None
            else {k: None for k in PER_INSTRUCTION_KEYS}
        )
        # pre-facade keyword layout (cpi=..., fetch_lat=..., ...)
        for k, v in legacy.items():
            if k in PER_INSTRUCTION_KEYS:
                self._arrays[k] = v
            else:
                self.metrics[k] = v

    @property
    def available_metrics(self) -> Tuple[str, ...]:
        """Scalar metric names plus whichever per-instruction arrays were
        actually collected."""
        return tuple(self.metrics) + tuple(
            k for k, v in self._arrays.items() if v is not None
        )

    def __getattr__(self, name: str):
        if name.startswith("_"):
            raise AttributeError(name)
        d = self.__dict__
        metrics = d.get("metrics", {})
        if name in metrics:
            return metrics[name]
        arrays = d.get("_arrays", {})
        if name in arrays:
            v = arrays[name]
            if v is None:
                raise MetricNotCollectedError(
                    f"per-instruction array {name!r} was not collected "
                    f"(metrics stayed on device): simulate with collect=True "
                    f"(EngineConfig.collect). available_metrics="
                    f"{self.available_metrics}"
                )
            return v
        raise MetricNotComputedError(
            f"metric {name!r} was not computed by this simulation; "
            f"available_metrics={self.available_metrics} (request its "
            f"MetricSpec via EngineConfig.metrics / simulate(metrics=...))"
        )

    def error_vs(self, truth_cpi: float) -> float:
        return abs(self.cpi - truth_cpi) / truth_cpi * 100.0

    def to_dict(self, *, arrays: bool = False) -> Dict:
        """Stable JSON-clean form (the serve layer's wire contract):
        scalar metrics as floats, phase-curve metrics as lists, collected
        per-instruction arrays only under ``arrays=True`` (they are
        O(trace) large)."""
        out = {
            "num_instructions": int(self.num_instructions),
            "seconds": float(self.seconds),
            "mips": float(self.mips),
            "metrics": {
                k: (np.asarray(v).tolist() if isinstance(v, np.ndarray) else float(v))
                for k, v in self.metrics.items()
            },
            "available_metrics": list(self.available_metrics),
        }
        if arrays:
            out["arrays"] = {
                k: np.asarray(v).tolist()
                for k, v in self._arrays.items()
                if v is not None
            }
        return out

    def __repr__(self) -> str:
        scalars = ", ".join(
            f"{k}=curve{v.shape}" if isinstance(v, np.ndarray) else f"{k}={v:.4g}"
            for k, v in self.metrics.items()
        )
        collected = [k for k, v in self._arrays.items() if v is not None]
        return (
            f"SimulationResult(n={self.num_instructions}, {scalars}, "
            f"mips={self.mips:.4g}, collected={collected})"
        )


class _CachedStep:
    """A jitted step shared across engines with identical (cfg, ecfg):
    params are an argument, so design-space sweeps that train many models
    of the same shape reuse one executable.

    ``aot`` holds the ahead-of-time compiled executable once
    ``StreamingEngine.warmup`` has lowered the geometry (single-device
    plans only — a sharded call site infers shardings from its concrete
    arguments); engines dispatch ``aot or fn``.  ``est_bytes`` is the
    retained-bytes estimate ``cache_stats`` aggregates, known only for
    AOT-compiled entries.
    """

    __slots__ = ("fn", "compiles", "aot", "est_bytes")

    def __init__(self):
        self.fn = None
        self.compiles = 0
        self.aot = None
        self.est_bytes = None

    def __call__(self, params, carry, batch):
        # direct drivers (tests, custom loops) call the entry like the old
        # bare jitted step; always through ``fn`` — an AOT executable pins
        # input layouts (committed device params), which arbitrary callers
        # don't guarantee.  Engines pick ``aot`` themselves in simulate().
        return self.fn(params, carry, batch)


_STEP_CACHE: Dict[tuple, _CachedStep] = {}

# entry-reuse counters behind cache_stats(): a hit means an engine needed a
# step and an already-built entry (its own or the process cache's) served
# it; a miss means a new jitted step was constructed
_STEP_STATS: Dict[str, int] = {"hits": 0, "misses": 0}


def cache_stats() -> Dict[str, int]:
    """Inspect the process-wide step cache: entry count, hit/miss
    counters, trace-time compiles, and estimated retained executable bytes
    (measured for AOT-warmed entries; ``entries_unmeasured`` counts
    lazily-jitted entries whose executables the estimate cannot see)."""
    measured = [e.est_bytes for e in _STEP_CACHE.values() if e.est_bytes]
    return {
        "entries": len(_STEP_CACHE),
        "hits": _STEP_STATS["hits"],
        "misses": _STEP_STATS["misses"],
        "compiles": sum(e.compiles for e in _STEP_CACHE.values()),
        "aot_compiled": sum(1 for e in _STEP_CACHE.values() if e.aot is not None),
        "retained_bytes_est": sum(measured),
        "entries_unmeasured": sum(
            1 for e in _STEP_CACHE.values() if not e.est_bytes
        ),
    }


def clear_step_cache() -> int:
    """Drop every cached step (returns how many were dropped).  Engines
    already holding an entry keep it alive until they are collected; new
    engines re-build.  Hit/miss counters keep accumulating — snapshot
    ``cache_stats()`` around a region to attribute its traffic."""
    n = len(_STEP_CACHE)
    _STEP_CACHE.clear()
    return n


@functools.partial(jax.jit, static_argnums=1)
def _per_head(tree, heads: int):
    """``tree`` repeated on a new leading axis of ``heads`` (one carry per
    head), in one dispatch."""
    return jax.tree.map(lambda x: jnp.broadcast_to(x, (heads,) + x.shape), tree)


class StreamingEngine:
    """Compile once, stream any number of traces.

    An engine instance owns the jitted steps for a (params-structure,
    TaoConfig, EngineConfig) triple; ``num_compiles`` counts actual traces
    of the step function, which the test suite pins to one per geometry
    (batch rows, effective window) that ``batch_rows`` plans.
    """

    # ``simulate`` takes a functional trace and, optionally, its host
    # FeatureSet (which ``TrainedModel`` keeps in the artifact store)
    takes_features = True

    def __init__(
        self,
        params: Dict,
        cfg: TaoConfig,
        ecfg: EngineConfig = EngineConfig(),
        *,
        qparams: Optional[Dict] = None,
        heads: int = 1,
    ):
        if ecfg.batch_size < 1:
            raise ValueError(f"batch_size must be >= 1, got {ecfg.batch_size}")
        if ecfg.feature_backend not in FEATURE_BACKENDS:
            # a store written earlier may still name the staged backend
            hint = (
                '; the staged "pallas" backend was replaced by "fused"'
                if ecfg.feature_backend == "pallas" else ""
            )
            raise ValueError(
                f"feature_backend must be one of {FEATURE_BACKENDS}, "
                f"got {ecfg.feature_backend!r}{hint}"
            )
        if ecfg.precision not in PRECISIONS:
            raise ValueError(
                f"precision must be one of {PRECISIONS}, "
                f"got {ecfg.precision!r}"
            )
        if heads < 1:
            raise ValueError(f"heads must be >= 1, got {heads}")
        if heads > 1:
            lead = {
                tuple(getattr(x, "shape", ()))[:1]
                for x in jax.tree_util.tree_leaves(params)
            }
            if lead != {(heads,)}:
                raise ValueError(
                    f"heads={heads} needs every params leaf stacked on a "
                    f"leading axis of {heads} models; leading axes {sorted(lead)}"
                )
        self.heads = heads
        self._specs: Tuple[MetricSpec, ...] = resolve_metrics(ecfg.metrics)
        for s in self._specs:
            if s.name == _GRID_KEY:
                raise ValueError(
                    f"metric name {_GRID_KEY!r} is reserved for the "
                    "engine's window-grid carry"
                )
        # one partitioning decision for everything this engine does:
        # placement, shard_map wrapping, index mapping, reductions
        self.plan = ExecutionPlan.resolve(
            ecfg.mesh, batch_size=ecfg.batch_size, plan=ecfg.plan
        )
        self.plan.validate_batch(ecfg.batch_size)
        self.params = params
        self.cfg = cfg
        self.ecfg = ecfg
        # pre-quantized int8 tree (registry/store path); lazily computed
        # from the fp32 params otherwise when precision="int8"
        self._qparams = qparams
        # geometry (batch rows, effective window) -> step
        self._steps: Dict[Tuple[int, int], _CachedStep] = {}
        # extraction programs launched (fused: one per batch), for the
        # sweep scheduler's counters
        self.extractions = 0
        # the all-zero part of every request's initial device state, built
        # on first use (``_zero_state``); everything it depends on — the
        # feature config, specs, heads and plan — is fixed per engine
        self._zeros: Optional[Tuple[Dict, Optional[Dict]]] = None
        self._zeros_lock = threading.Lock()  # a server may simulate from two threads
        self.state_builds = 0

    @property
    def num_compiles(self) -> int:
        """Traces of the step function across every step this engine used
        (shared with other engines of identical config — at most one per
        geometry and params structure either way)."""
        return sum(e.compiles for e in self._steps.values())

    def batch_rows(self, n: int) -> Tuple[int, ...]:
        """The rows of each batch a trace of ``n`` instructions runs, in
        order: ``w // batch_size`` full batches of its ``w`` windows, then
        the rest, if any, as one tail batch.  The tail runs on half the
        rows when it holds at most half a batch, the batch size is even
        and the half still divides over the plan's shards; otherwise on
        the full rows.  Both feature backends stream this plan."""
        if n < 1:
            raise ValueError("cannot simulate an empty trace")
        bsz = self.ecfg.batch_size
        full, rem = divmod(num_windows(n, self.cfg.window, self.cfg.window), bsz)
        if not rem:
            return (bsz,) * full
        half = bsz // 2
        tail = half if rem <= half and bsz % 2 == 0 and self.plan.divides(half) else bsz
        return (bsz,) * full + (tail,)

    # ---- jitted step ---------------------------------------------------

    # tao: step-builder[engine-step] ignore=entry
    def _build_step(self, rows: int, w_eff: int, entry: _CachedStep):
        cfg = self.cfg
        collect = self.ecfg.collect
        plan = self.plan
        actx = plan.axis_context()
        specs = self._specs
        bsz_global = rows
        # trace-time branch: the fp32 forward or its W8A8 quantized twin
        # (the choice is baked into the executable, hence the cache key)
        forward = tao_forward if self.ecfg.precision == "fp32" else tao_forward_int8

        def body(params, carry, batch):
            entry.compiles += 1  # runs at trace time only
            valid = batch["valid"].reshape(-1)
            out = forward(params, {k: batch[k] for k in INPUT_KEYS}, cfg)
            fetch = jnp.maximum(out["fetch_lat"], 0.0).reshape(-1)
            execl = jnp.maximum(out["exec_lat"], 0.0).reshape(-1)
            misp = jax.nn.sigmoid(out["mispred_logit"]).reshape(-1)
            dlev = jnp.argmax(out["dlevel_logits"], -1).astype(jnp.int32).reshape(-1)
            on = valid > 0
            br = batch["is_branch"].reshape(-1) & on
            mem = batch["is_mem"].reshape(-1) & on

            n_local = valid.shape[0]
            shard = actx.shard_index()  # 0 on the single-device plan
            gidx = (shard * n_local + jnp.arange(n_local)).astype(jnp.float32)
            # key of the globally-last valid position (-1 when none local)
            last_key = actx.pmax(jnp.max(jnp.where(on, gidx, -1.0)))

            # trace-global window index of each local row, from the grid
            # carry (windowed specs scatter phase contributions with it)
            grid = carry[_GRID_KEY]
            b_local = batch["valid"].shape[0]
            win_index = (
                grid["seen"]
                + shard * b_local
                + jnp.arange(b_local, dtype=jnp.int32)
            )

            ctx = StepContext(
                valid=valid,
                on=on,
                is_branch=br,
                is_mem=mem,
                fetch_lat=fetch,
                exec_lat=execl,
                mispred_prob=misp,
                dlevel=dlev,
                gidx=gidx,
                last_key=last_key,
                psum=actx.psum,
                pmax=actx.pmax,
                sharded=plan.sharded,
                batch=batch,
                window=w_eff,
                win_index=win_index,
                num_windows=grid["total"],
            )
            new_carry = {s.name: s.update(carry[s.name], ctx) for s in specs}
            new_carry[_GRID_KEY] = {
                "seen": grid["seen"] + jnp.int32(bsz_global),
                "total": grid["total"],
            }
            if collect:
                per = {
                    "fetch_lat": fetch,
                    "exec_lat": execl,
                    "mispred_prob": misp,
                    "dlevel": dlev,
                }
            else:
                per = {}
            return new_carry, per

        step = body
        if self.heads > 1:
            # every head over the one shared batch: params and carry carry
            # the leading K axis, per-instruction outputs come out (K, rows)
            step = jax.vmap(body, in_axes=(0, 0, None))
        if not plan.sharded:
            return jax.jit(step)

        batched = plan.batch_spec()
        batch_specs = {
            k: batched for k in INPUT_KEYS + ("valid", "is_branch", "is_mem")
        }
        per_spec = P(None, *batched) if self.heads > 1 else batched
        per_specs = (
            {k: per_spec for k in PER_INSTRUCTION_KEYS} if collect else {}
        )
        mapped = plan.wrap(
            step,
            in_specs=(P(), P(), batch_specs),
            out_specs=(P(), per_specs),
        )
        return jax.jit(mapped)

    def _get_step(self, geometry: Tuple[int, int]) -> _CachedStep:
        """The step entry of one batch geometry, ``(rows, w_eff)``."""
        entry = self._steps.get(geometry)
        if entry is None:
            rows, w_eff = geometry
            # Keyed on exactly what the compiled step depends on — notably
            # NOT prefetch or feature_backend, so "numpy" and "fused"
            # engines of the same shape share one executable
            # (precision IS keyed: int8 bakes a different forward).  The
            # resolved plan (not the raw mesh) is the partitioning key, so
            # EngineConfig(mesh=m) and EngineConfig(plan=resolve(m)) also
            # share one.  The batch's rows, not the engine's batch size:
            # a half-batch tail and a full batch of that size are one step.
            key = (  # tao: step-key[engine-step]
                self.cfg,
                rows,
                self.ecfg.collect,
                self.ecfg.precision,
                self.plan,
                self._specs,
                w_eff,
            )
            if self.heads > 1:  # a one-model engine keeps its step's key
                key += (self.heads,)  # tao: step-key[engine-step]
            entry = _STEP_CACHE.get(key)
            if entry is None:
                fault_point("engine.compile", payload=f"w{w_eff}")
                _STEP_STATS["misses"] += 1
                entry = _CachedStep()
                entry.fn = self._build_step(rows, w_eff, entry)
                _STEP_CACHE[key] = entry
            else:
                _STEP_STATS["hits"] += 1
            self._steps[geometry] = entry
        else:
            _STEP_STATS["hits"] += 1
        return entry

    def _zero_state(self) -> Tuple[Dict, Optional[Dict]]:
        """The all-zero part of a request's initial device state: the step
        carry without the grid's ``total`` (every spec's ``init()`` and the
        grid's ``seen``, per head when ``heads > 1``, placed as the plan
        places the carry) and, on the "fused" backend, the extraction's
        scan carry.  Built once and handed to every request unchanged:
        neither the step nor the extraction program donates its inputs.
        A build under a trace (``jax.eval_shape`` over ``init_carry``)
        yields tracers and is not kept."""
        with self._zeros_lock:
            if self._zeros is not None:
                return self._zeros
            carry = {s.name: s.init() for s in self._specs}
            carry[_GRID_KEY] = {"seen": jnp.zeros((), jnp.int32)}
            if self.heads > 1:
                carry = _per_head(carry, self.heads)
            carry = self.plan.replicate(carry)
            scan = None
            if self.ecfg.feature_backend == "fused":
                from ..kernels.fused.ops import init_fused_state  # lazy: module note

                scan = init_fused_state(self.cfg.features)
            zeros = (carry, scan)
            if not any(
                isinstance(x, jax.core.Tracer) for x in jax.tree_util.tree_leaves(zeros)
            ):
                self._zeros = zeros
                self.state_builds += 1
            return zeros

    def init_carry(self, n: int) -> Dict:
        """The initial carry for a trace of ``n`` instructions: every
        requested spec's ``init()`` plus the engine's reserved window-grid
        slot (running window offset + total windows — what windowed specs
        scatter phase contributions with).  Code driving the jitted step
        directly (custom batch columns via ``stream_batches(extra=...)``)
        must start from this, not a hand-built spec dict.

        The zero leaves are the engine's kept ones (``_zero_state``); the
        grid's ``total`` is the one new device value, one host put."""
        if n < 1:
            raise ValueError("cannot simulate an empty trace")
        nw = num_windows(n, self.cfg.window, self.cfg.window)
        for s in self._specs:
            # chunk_of's bucket math (win_index * num_chunks) is int32;
            # refuse traces that would silently wrap into bucket 0
            if s.num_chunks is not None and nw * s.num_chunks > 2**31 - 1:
                raise ValueError(
                    f"windowed spec {s.name!r}: num_windows ({nw}) * "
                    f"num_chunks ({s.num_chunks}) exceeds the int32 "
                    "chunk-index envelope; reduce num_chunks or split "
                    "the trace"
                )
        total = np.full((self.heads,) if self.heads > 1 else (), nw, np.int32)
        # placed where the step's replicated output carry lives, so the
        # first call and every later one trace to the same program
        total = self.plan.replicate(total) if self.plan.sharded else jax.device_put(total)
        # fresh containers over the kept leaves: a caller's edits to the
        # returned dict never reach the next request
        carry = jax.tree.map(lambda x: x, self._zero_state()[0])
        carry[_GRID_KEY]["total"] = total
        return carry

    def step_entries_for(self, n: int) -> Tuple[_CachedStep, ...]:
        """The cached step entries ``simulate`` will use for a trace of
        length ``n``, one per geometry of ``batch_rows(n)`` in order of
        first use (created lazily; their ``compiles`` counters let callers
        like the sweep scheduler attribute compilations precisely)."""
        w_eff = min(self.cfg.window, n)
        rows = dict.fromkeys(self.batch_rows(n))
        return tuple(self._get_step((r, w_eff)) for r in rows)

    # ---- ahead-of-time compilation --------------------------------------

    def _abstract_batch(self, b: int, w_eff: int) -> Dict:
        """ShapeDtypeStructs of one step batch of ``b`` rows — the exact
        shapes/dtypes ``stream_batches`` (and the fused extractor, which is
        bit-compatible) produces for that geometry."""
        f = self.cfg.features
        sds = jax.ShapeDtypeStruct
        return {
            "opcode": sds((b, w_eff), jnp.int32),
            "regbits": sds((b, w_eff, NUM_REGS), jnp.float32),
            "flags": sds((b, w_eff, f.flags_dim), jnp.float32),
            "brhist": sds((b, w_eff, f.n_queue), jnp.float32),
            "memdist": sds((b, w_eff, f.n_mem), jnp.float32),
            "valid": sds((b, w_eff), jnp.float32),
            "is_branch": sds((b, w_eff), jnp.bool_),
            "is_mem": sds((b, w_eff), jnp.bool_),
        }

    def warmup(self, n: int) -> Tuple[_CachedStep, ...]:
        """Compile the steps for traces of length ``n`` ahead of time: one
        per geometry of ``batch_rows(n)`` (the full batch, the half-batch
        tail, or both), returned as ``step_entries_for(n)`` gives them.

        Lowers from abstract (ShapeDtypeStruct) params and batch — so the
        engine may hold abstract params from ``jax.eval_shape`` — and
        compiles through the XLA client, populating the persistent
        compilation cache when ``engine.aot.enable_persistent_cache`` has
        pointed one at disk.  On a single-device, single-process plan the
        compiled executable is pinned on the entry and dispatched directly
        by ``simulate`` (zero retrace, zero dispatch-time lowering); on
        sharded plans the entry still gets built and traced (the warm
        persistent cache then serves the sharded call's own compile), but
        dispatch stays with the jitted function, which owns the
        shard-placement inference.  Idempotent per geometry.
        """
        entries = self.step_entries_for(n)
        if self.plan.sharded or jax.process_count() > 1:
            return entries
        w_eff = min(self.cfg.window, n)
        for rows, entry in zip(dict.fromkeys(self.batch_rows(n)), entries):
            if entry.aot is not None:
                continue
            lowered = entry.fn.lower(
                abstract_like(self._run_params()),
                abstract_like(self.init_carry(n)),
                self._abstract_batch(rows, w_eff),
            )
            compiled = lowered.compile()
            entry.est_bytes = compile_bytes_estimate(compiled)
            entry.aot = compiled
        return entries

    def _run_params(self):
        """The parameter tree the step actually consumes: the engine's
        fp32 tree, or (``precision="int8"``) its quantized twin — the
        injected pre-quantized ``qparams`` when the api/registry layer
        resolved one from the ArtifactStore, otherwise computed once here
        (``jax.eval_shape`` keeps abstract param trees abstract, so AOT
        warmup works either way)."""
        if self.ecfg.precision != "int8":
            return self.params
        q = self._qparams
        if q is None:
            quantize = (
                jax.vmap(quantize_tao_params) if self.heads > 1
                else quantize_tao_params
            )
            leaves = jax.tree_util.tree_leaves(self.params)
            if any(isinstance(x, jax.ShapeDtypeStruct) for x in leaves):
                q = jax.eval_shape(quantize, self.params)
            else:
                q = quantize(self.params)
            self._qparams = q
        return q

    def _committed_params(self):
        """Run params as committed device arrays (what an AOT executable's
        input layout expects); transferred once per engine."""
        p = getattr(self, "_dev_params", None)
        if p is None:
            p = jax.device_put(self._run_params())
            self._dev_params = p
        return p

    # ---- streaming -----------------------------------------------------

    def _prefetched(self, host_batches: Iterator[Dict]) -> Iterator[Dict]:
        """Enqueue batch i+1's transfer before batch i is consumed (inline
        on CPU, threaded producer on accelerator backends); placement is
        the plan's."""
        return prefetch_to_device(host_batches, self.plan.device_put)

    def _fused_batches(
        self, cols: Dict, w_eff: int, count: int
    ) -> Iterator[Dict]:
        """Batch iterator for the "fused" backend: the raw int32/bool
        columns stay on the host, and every batch is ONE dispatch of one
        compiled extraction program (``kernels/fused/``) fed one packed
        host array, with the scan state carried across batches — model
        inputs are produced per batch and consumed by the step
        immediately, so no O(trace) feature materialization ever exists.
        Window/padding/validity layout and the batch rows are
        ``stream_batches``'s under ``batch_rows`` (bit-identical by
        construction)."""
        from ..kernels.fused.ops import FusedExtractor  # lazy: module note

        rows = self.batch_rows(count)
        # the scan starts from the engine's kept zero carry: no device op
        # here, the request's one put is init_carry's
        extractor = FusedExtractor(
            {k: v[:count] for k, v in cols.items()},
            self.cfg.features,
            pad_to=sum(rows) * w_eff,
            state=self._zero_state()[1],
        )
        for r in rows:
            with span("fused.extract"):
                batch = extractor.next_batch(r * w_eff, (r, w_eff))
                self.extractions += 1
                if self.plan.sharded:
                    batch = self.plan.device_put(batch)
            yield batch

    def simulate(
        self,
        func_trace: np.ndarray,
        features: Optional[FeatureSet] = None,
    ) -> SimulationResult:
        """Stream one trace through a one-model engine's step."""
        if self.heads > 1:
            raise ValueError(
                f"a {self.heads}-head engine gives one result per head: "
                "use simulate_heads"
            )
        return self.simulate_heads(func_trace, features)[0]

    # tao: hot
    def simulate_heads(
        self,
        func_trace: np.ndarray,
        features: Optional[FeatureSet] = None,
    ) -> List[SimulationResult]:
        """Stream one trace through the step: one ``SimulationResult`` per
        head, in the order of the stacked axis (one for a one-model
        engine)."""
        t0 = time.perf_counter()
        fault_point("engine.simulate")
        cfg = self.cfg
        n = len(features) if features is not None else len(func_trace)
        if n == 0:
            raise ValueError("cannot simulate an empty trace")
        w_eff = min(cfg.window, n)
        # exact instruction count from the window grid (no float rounding)
        count = num_windows(n, cfg.window, cfg.window) * w_eff
        rows = self.batch_rows(n)
        with call_span(
            "engine.simulate",
            instructions=count,
            positions=sum(rows) * w_eff,
            batches=len(rows),
            half_batches=int(rows[-1] != self.ecfg.batch_size),
            heads=self.heads,
        ):
            steps = {}  # batch rows -> (callable, params)
            for r in dict.fromkeys(rows):
                entry = self._get_step((r, w_eff))
                # AOT-warmed geometry: call the compiled executable directly
                # (no dispatch-time retracing; params must be committed
                # device arrays)
                if entry.aot is not None:
                    steps[r] = (entry.aot, self._committed_params())
                else:
                    steps[r] = (entry.fn, self._run_params())

            if features is None and self.ecfg.feature_backend == "fused":
                from ..kernels.fused.ops import trace_columns  # lazy: module note

                # raises when addresses leave the int32-exact window: the
                # device backend the caller asked for never silently becomes
                # the NumPy one
                with span("engine.columns"):
                    cols = trace_columns(func_trace, cfg.features)
                batches = self._fused_batches(cols, w_eff, count)
            else:
                fs = features
                if fs is None:
                    with span("engine.columns"):
                        fs = extract_features(
                            func_trace, cfg.features, with_labels=False
                        )
                host_batches = stream_batches(
                    fs,
                    cfg.window,
                    self.ecfg.batch_size,
                    stride=cfg.window,
                    extra={
                        "is_branch": func_trace["is_branch"],
                        "is_mem": func_trace["is_mem"],
                    },
                    rows=rows,
                )
                batches = (
                    self._prefetched(host_batches)
                    if self.ecfg.prefetch
                    else (self.plan.device_put(b) for b in host_batches)
                )

            # specs' init plus the window-grid slot: running global window
            # offset + total real windows (data, not shape — every trace
            # shares the executable); the zeros are the engine's kept ones
            with span("engine.upload"):
                carry = self.init_carry(n)
            pers = []
            for batch, r in zip(batches, rows):
                step, params = steps[r]
                with span("engine.step"):
                    carry, per = step(params, carry, batch)
                if self.ecfg.collect:
                    pers.append(per)

            with span("engine.sync"):
                carry = jax.device_get(carry)  # single host sync for the whole trace
                if self.heads == 1:
                    finalized = [self._finalize(carry, count)]
                else:
                    finalized = [
                        self._finalize(jax.tree.map(lambda x: x[h], carry), count)
                        for h in range(self.heads)
                    ]
                secs = time.perf_counter() - t0

                if self.ecfg.collect and pers:
                    # one explicit sync for every batch's arrays (was a hidden
                    # np.asarray device->host pull per batch per key)
                    pers = jax.device_get(pers)
                results = []
                for h, metrics in enumerate(finalized):
                    arrays: Dict[str, Optional[np.ndarray]] = {
                        k: None for k in PER_INSTRUCTION_KEYS
                    }
                    if self.ecfg.collect and pers:
                        for k in arrays:
                            parts = [p[k] if self.heads == 1 else p[k][h] for p in pers]
                            arrays[k] = np.concatenate(parts)[:count]
                    results.append(SimulationResult(
                        num_instructions=count,
                        seconds=secs,
                        mips=count / 1e6 / secs,
                        metrics=metrics,
                        arrays=arrays,
                    ))
                return results

    def _finalize(self, carry: Dict, count: int) -> Dict[str, float]:
        """One head's host-side carry -> its finalized metrics."""
        metrics: Dict[str, float] = {}
        for s in self._specs:
            out = s.finalize(carry[s.name], count)
            clash = set(out) & set(metrics)
            if clash:
                raise ValueError(
                    f"metric spec {s.name!r} finalized key(s) {sorted(clash)} "
                    "already emitted by an earlier spec in this run"
                )
            reserved = set(out) & _RESERVED_RESULT_ATTRS
            if reserved:
                raise ValueError(
                    f"metric spec {s.name!r} finalized reserved key(s) "
                    f"{sorted(reserved)}: SimulationResult instance "
                    "attributes would shadow them"
                )
            metrics.update(out)
        return metrics


def simulate_trace_engine(
    params: Dict,
    func_trace: np.ndarray,
    cfg: TaoConfig,
    batch_size: int = 64,
    features: Optional[FeatureSet] = None,
    collect: bool = False,
    mesh: Optional[Mesh] = None,
    plan: Optional[ExecutionPlan] = None,
    feature_backend: str = "numpy",
    precision: str = "fp32",
    metrics: Tuple[Union[str, MetricSpec], ...] = DEFAULT_METRICS,
) -> SimulationResult:
    """One-shot convenience wrapper: build an engine, stream one trace."""
    engine = StreamingEngine(
        params,
        cfg,
        EngineConfig(
            batch_size=batch_size,
            collect=collect,
            mesh=mesh,
            plan=plan,
            feature_backend=feature_backend,
            precision=precision,
            metrics=metrics,
        ),
    )
    return engine.simulate(func_trace, features=features)
