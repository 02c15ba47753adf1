"""Declared wire schemas for the PR 7 JSON-lines serve protocol (TAO007).

These are the **contract**, written down once, here — the analyzer
statically extracts each class's ``to_dict`` key set and diffs it against
this registry, so a field added to (or dropped from) a result dataclass
cannot silently change what tenants parse.  Changing the wire format is
allowed; doing it without touching this file is not.

``required`` keys are always present in the emitted dict; ``optional``
keys are emitted conditionally (``SimulationResult.to_dict(arrays=True)``,
``ServeError`` retry/request-id hints).
"""
from __future__ import annotations

from typing import Dict, FrozenSet, NamedTuple


class WireSchema(NamedTuple):
    required: FrozenSet[str]
    optional: FrozenSet[str] = frozenset()
    # where the class lives (repo-relative suffix) — lets the analyzer
    # tell "class renamed away" from "that file was not scanned"
    home: str = ""


WIRE_SCHEMAS: Dict[str, WireSchema] = {
    # engine/runner.py — per-trace result
    "SimulationResult": WireSchema(
        home="engine/runner.py",
        required=frozenset(
            {
                "num_instructions",
                "seconds",
                "mips",
                "metrics",
                "available_metrics",
            }
        ),
        optional=frozenset({"arrays"}),
    ),
    # engine/scheduler.py — sweep counters + nested results
    "SweepReport": WireSchema(
        home="engine/scheduler.py",
        required=frozenset(
            {
                "seconds",
                "num_traces",
                "num_instructions",
                "queue_depth",
                "prepared_async",
                "traces_per_s",
                "mips",
                "num_compiles",
                "queue_occupancy_mean",
                "queue_occupancy_max",
                "plan_kind",
                "num_shards",
                "features_extracted",
                "features_from_store",
                "jobs_skipped",
                "heads_per_step",
                "extractions",
                "stacks_built",
                "results",
            }
        ),
    ),
    # serve/types.py — per-request wire result
    "ServeResult": WireSchema(
        home="serve/types.py",
        required=frozenset(
            {
                "request_id",
                "model",
                "tenant",
                "geometry",
                "num_instructions",
                "metrics",
                "queue_s",
                "extract_s",
                "compute_s",
                "total_s",
                "coalesced",
            }
        ),
    ),
    # serve/types.py — TraceServer.stats() observability snapshot
    "ServerStats": WireSchema(
        home="serve/types.py",
        required=frozenset(
            {
                "uptime_s",
                "admitted",
                "completed",
                "failed",
                "rejected",
                "queue_depth",
                "max_queue",
                "num_compiles",
                "features_extracted",
                "features_from_store",
                "features_coalesced",
                "traces_per_s",
                "latency_p50_s",
                "latency_p99_s",
                "queue_p50_s",
                "queue_p99_s",
                "batch_fill_ratio",
                "plan_kind",
                "num_shards",
                "retries",
                "deadline_exceeded",
                "quarantined",
                "bisections",
                "breaker_sheds",
                "breakers",
                "per_geometry",
                "per_tenant",
            }
        ),
    ),
    # serve/types.py — stable error surface
    "ServeError": WireSchema(
        home="serve/types.py",
        required=frozenset({"error", "message"}),
        optional=frozenset({"retry_after_s", "request_id"}),
    ),
}


# The closed ServeError code vocabulary, declared here exactly like the
# dict shapes above: TAO007 statically reads the ``ERROR_CODES`` tuple in
# serve/types.py and diffs it against this set, so a code added to (or
# dropped from) the failure surface cannot skip the contract review.
WIRE_ERROR_CODES: FrozenSet[str] = frozenset(
    {
        "QUEUE_FULL",
        "UNKNOWN_MODEL",
        "BAD_REQUEST",
        "GEOMETRY_MISMATCH",
        "METRIC_NOT_COMPUTED",
        "METRIC_NOT_COLLECTED",
        "SHUTTING_DOWN",
        "DEADLINE_EXCEEDED",
        "TRACE_REJECTED",
        "CIRCUIT_OPEN",
        "INTERNAL",
    }
)
