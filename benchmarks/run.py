"""Benchmark harness — one module per paper table/figure.

  bench_accuracy   Fig 9     accuracy vs SimNet baseline
  bench_timing     Table 4 + Fig 10   trace economics / end-to-end time
  bench_sweeps     Fig 12    feature-parameter sweeps (N_m, N_b, N_q)
  bench_transfer   Fig 13/14 + Table 5/6  agnostic embeddings + transfer
  bench_dse        Fig 15    design-space exploration
                   + "sweep": async Session.sweep scheduler stats
                     (traces/s, compiles, queue occupancy)
                   + "coldstart": first-result latency cold vs warm
                     persistent caches (artifact store + XLA executables)
  bench_train      (systems) streaming vs materialized training pipeline
                     (windows/s, peak RSS, compile counts)
  bench_kernels    (systems) chunked attention / SSD formulations
  bench_serve      (systems) "serve": open-loop multi-tenant TraceServer
                     load (p50/p99 latency, traces/s, batch fill ratio)
  bench_resilience (systems) "resilience": degraded-mode tail latency
                     under a seeded fault plan + breaker recovery time
                     (CI uploads ``BENCH_resilience.json``)

Prints ``name,us_per_call,derived`` CSV.  BENCH_SCALE=tiny|small|full
controls trace lengths / epochs (CPU container defaults to small; CI smoke
uses tiny).  Run a subset: ``python -m benchmarks.run --only fig9,table4``.
``--json PATH`` additionally writes the rows as structured JSON (the CI
bench-smoke job uploads ``BENCH_timing.json``, ``BENCH_dse.json``, and ``BENCH_train.json`` as
artifacts so the perf trajectory — including the async sweep scheduler's
numbers — is tracked per PR).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback

from . import (
    bench_accuracy,
    bench_dse,
    bench_kernels,
    bench_resilience,
    bench_serve,
    bench_shard,
    bench_sweeps,
    bench_timing,
    bench_train,
    bench_transfer,
)
from .common import SCALE, emit, extras, rows

SUITES = {
    "fig9": bench_accuracy.run,
    "table4": bench_timing.run,
    "fig12": bench_sweeps.run,
    "fig13_14_t5": bench_transfer.run,
    "fig15": bench_dse.run,
    "sweep": bench_dse.run_sweep,
    "coldstart": bench_dse.run_coldstart,
    "training": bench_train.run,
    "kernels": bench_kernels.run,
    "shard": bench_shard.run,
    "serve": bench_serve.run,
    "resilience": bench_resilience.run,
}


def _write_json(path: str) -> None:
    # device/mesh topology + persistent-cache status ride along so
    # artifacts from different hosts (CI runners, TPU pods, laptops) are
    # comparable at a glance — and so a bench run against a warm compile
    # cache is distinguishable from a truly cold one
    from repro.distributed import topology_info
    from repro.engine import persistent_cache_status

    records = []
    for row in rows():
        name, us, derived = row.split(",", 2)
        records.append(
            {"name": name, "us_per_call": float(us), "derived": derived}
        )
    payload = {
        "scale": SCALE,
        "topology": topology_info(),
        "persistent_cache": persistent_cache_status(),
        "rows": records,
        **extras(),
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"wrote {path} ({len(records)} rows)", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="comma-separated suite names")
    ap.add_argument("--json", default=None, help="also write rows to this JSON file")
    args = ap.parse_args()
    names = list(SUITES) if not args.only else args.only.split(",")
    # coldstart's child processes need the device, which this process
    # holds once any other suite has run
    names.sort(key=lambda n: n != "coldstart")

    # The persistent compile cache ($JAX_COMPILATION_CACHE_DIR, else the
    # checkout's .cache/jax) carries compiled executables across bench runs:
    # first-run compile time disappears from later runs without touching
    # any measured steady-state number — every suite warms up before its
    # timed section.
    from repro.engine import enable_persistent_cache

    enable_persistent_cache()

    print("name,us_per_call,derived")
    t0 = time.time()
    failures = 0
    for name in names:
        try:
            t = time.time()
            SUITES[name]()
            emit(f"{name}/total", (time.time() - t) * 1e6, "ok")
        except Exception as e:  # record and continue
            failures += 1
            emit(f"{name}/total", 0.0, f"FAILED:{type(e).__name__}:{e}")
            traceback.print_exc()
    emit("all/total", (time.time() - t0) * 1e6, f"failures={failures}")
    if args.json:
        _write_json(args.json)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
