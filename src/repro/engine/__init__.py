"""Streaming simulation engine: the device-resident §4.2 inference path.

See ``docs/engine.md`` for the data-flow architecture and
``benchmarks/bench_timing.py`` for the measured speedup over the legacy
host-loop path (``repro.core.simulate.simulate_trace_legacy``).  Metric
accumulators are pluggable (``engine.metrics``); multi-trace DSE sweeps
run through the async scheduler (``engine.scheduler``).  SimNet, whose
predictions feed its own inputs, runs a sequential scan over parallel
sub-traces (``engine.sequential``); ``make_engine`` picks by model kind.
"""
from .metrics import (
    DEFAULT_METRICS,
    DEFAULT_PHASE_CHUNKS,
    METRIC_REGISTRY,
    MetricSpec,
    StepContext,
    register_metric,
    resolve_metrics,
    windowed_spec,
)
from .aot import (
    enable_persistent_cache,
    persistent_cache_status,
    xla_cache_counters,
)
from .plan import AxisContext, ExecutionPlan
from .runner import (
    FEATURE_BACKENDS,
    PER_INSTRUCTION_KEYS,
    PRECISIONS,
    EngineConfig,
    MetricNotCollectedError,
    MetricNotComputedError,
    SimulationResult,
    StreamingEngine,
    cache_stats,
    clear_step_cache,
    simulate_trace_engine,
)
from .scheduler import SweepJob, SweepReport, TraceSweeper, sweep_traces
from .sequential import SimNetEngine, SimNetResult, make_engine

__all__ = [
    "AxisContext",
    "ExecutionPlan",
    "cache_stats",
    "clear_step_cache",
    "enable_persistent_cache",
    "persistent_cache_status",
    "xla_cache_counters",
    "EngineConfig",
    "FEATURE_BACKENDS",
    "PER_INSTRUCTION_KEYS",
    "PRECISIONS",
    "DEFAULT_METRICS",
    "DEFAULT_PHASE_CHUNKS",
    "METRIC_REGISTRY",
    "MetricSpec",
    "StepContext",
    "register_metric",
    "resolve_metrics",
    "windowed_spec",
    "MetricNotCollectedError",
    "MetricNotComputedError",
    "SimNetEngine",
    "SimNetResult",
    "SimulationResult",
    "StreamingEngine",
    "make_engine",
    "simulate_trace_engine",
    "SweepJob",
    "SweepReport",
    "TraceSweeper",
    "sweep_traces",
]
