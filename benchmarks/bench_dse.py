"""Fig. 15 — hardware design-space exploration with Tao: L1D-size sweep
(cache MPKI) and branch-predictor sweep (branch MPKI), prediction vs the
detailed simulator's ground truth — plus the async multi-trace sweep
scheduler's tracked perf numbers (``run_sweep``; ROADMAP "async multi-trace
scheduling")."""
from __future__ import annotations

import dataclasses
import math

import numpy as np

from repro.api import DesignSpace, TrainedModel
from repro.uarch import UARCH_B

from .common import (
    EPOCHS,
    TEST_BENCHES,
    TEST_LEN,
    TRAIN_BENCHES,
    Timer,
    adjusted_dataset,
    emit,
    session,
)


def _model_for(uarch) -> TrainedModel:
    """Train a model for the design point; its engines come from the
    process-wide step cache, so every design point of the sweep reuses one
    compiled executable."""
    sess = session()
    ds = adjusted_dataset(uarch, TRAIN_BENCHES[:2])
    return sess.train(
        dataset=ds, epochs=max(3, EPOCHS // 2), batch_size=16, lr=1e-3,
        name=uarch.name, uarch=uarch,
    )


def run() -> None:
    sess = session()
    # Fig 15a: L1 D-cache size sweep — does predicted MPKI track the truth?
    truth_curve, pred_curve = [], []
    for size_kb in (16, 32, 128):
        ua = dataclasses.replace(
            UARCH_B, l1d_size=size_kb * 1024, name=f"l1d{size_kb}"
        )
        model = _model_for(ua)
        t_mpki, p_mpki = [], []
        for bench in TEST_BENCHES[:2]:
            tr = sess.capture(bench, TEST_LEN)
            truth = sess.ground_truth(ua, tr)
            sim = model.simulate(tr)
            t_mpki.append(truth["l1d_mpki"])
            p_mpki.append(sim.l1d_mpki)
        truth_curve.append(float(np.mean(t_mpki)))
        pred_curve.append(float(np.mean(p_mpki)))
        emit(
            f"fig15a/l1d={size_kb}KB",
            0.0,
            f"truth_l1d_mpki={truth_curve[-1]:.2f};tao_l1d_mpki={pred_curve[-1]:.2f}",
        )
    mono_truth = all(np.diff(truth_curve) <= 1e-9)
    mono_pred = all(np.diff(pred_curve) <= max(1.0, 0.1 * pred_curve[0]))
    emit("fig15a/trend", 0.0,
         f"truth_monotone={mono_truth};tao_tracks_trend={mono_pred}")

    # Fig 15b: branch predictor sweep
    for bp in ("Local", "BiMode", "Tournament"):
        ua = dataclasses.replace(UARCH_B, branch_predictor=bp, name=f"bp{bp}")
        model = _model_for(ua)
        t_mpki, p_mpki = [], []
        for bench in TEST_BENCHES[:2]:
            tr = sess.capture(bench, TEST_LEN)
            truth = sess.ground_truth(ua, tr)
            sim = model.simulate(tr)
            t_mpki.append(truth["branch_mpki"])
            p_mpki.append(sim.branch_mpki)
        emit(
            f"fig15b/bp={bp}",
            0.0,
            f"truth_br_mpki={np.mean(t_mpki):.2f};tao_br_mpki={np.mean(p_mpki):.2f}",
        )


def run_sweep() -> None:
    """Async multi-trace DSE sweep (Session.sweep): 4 design points x 2
    traces through one shared executable, vs the same jobs run one-by-one
    through single-trace engines (per-trace host prep on the critical
    path)."""
    sess = session()
    space = DesignSpace.vary(
        UARCH_B, "l1d_size", [kb * 1024 for kb in (16, 32, 64, 128)],
        name_fmt="l1d{value}",
    )
    models = {ua.name: _model_for(ua) for ua in space}
    traces = {b: sess.capture(b, TEST_LEN) for b in TEST_BENCHES[:2]}

    # warm both steps once so BOTH paths below measure steady-state
    # throughput (neither is charged the one-off XLA compile): the
    # one-model step of the sequential path, and the stacked step that
    # runs every model of the sweep over each batch
    first = next(iter(models.values()))
    first.simulate(next(iter(traces.values())), batch_size=sess.batch_size)
    sess.warmup([len(tr.functional) for tr in traces.values()], heads=len(models))

    # baseline: the single-trace engine path, sequential over the same jobs
    # (per-trace host feature prep repeats per model on the critical path).
    # Best-of-N on both paths: the structural deltas are a few percent at
    # tiny scale, so single runs drown in 2-core scheduler noise.
    reps = 3
    seq_secs, n_seq = float("inf"), 0
    for _ in range(reps):
        with Timer() as t_seq:
            n_seq = 0
            for model in models.values():
                for tr in traces.values():
                    n_seq += model.simulate(
                        tr, batch_size=sess.batch_size
                    ).num_instructions
        seq_secs = min(seq_secs, t_seq.seconds)
    seq_mips = n_seq / 1e6 / seq_secs
    seq_tps = len(models) * len(traces) / seq_secs

    report = None
    for _ in range(reps):
        r = sess.sweep(models, traces)
        # the cache is warm, so the sweep itself must compile nothing
        assert r.num_compiles == 0, r.num_compiles
        if report is None or r.seconds < report.seconds:
            report = r
    emit(
        "sweep/scheduler",
        1e6 * report.seconds / report.num_traces,
        f"uarchs={len(models)};traces={len(traces)};"
        f"traces_per_s={report.traces_per_s:.2f};sweep_mips={report.mips:.4f};"
        f"single_engine_mips={seq_mips:.4f};single_engine_traces_per_s={seq_tps:.2f};"
        f"speedup={report.mips / seq_mips:.2f}x;"
        f"compiles={report.num_compiles};"
        f"queue_occupancy_mean={report.queue_occupancy_mean:.2f};"
        f"queue_occupancy_max={report.queue_occupancy_max};"
        f"queue_depth={report.queue_depth};"
        f"prepared_async={report.prepared_async}",
    )
    # predictions from the sweep match the single-engine path to a relative
    # 1e-6: the stacked step is its own XLA program, whose float32 sums may
    # round apart in the last bits (tests/test_sweep_stacked.py)
    for name, model in models.items():
        for tb, tr in traces.items():
            a = report.results[f"{name}/{tb}"]
            b = model.simulate(tr, batch_size=sess.batch_size)
            for m in ("cpi", "l1d_mpki"):
                assert math.isclose(
                    a.metrics[m], b.metrics[m], rel_tol=1e-6, abs_tol=0.0
                ), (name, tb, m)


# ---------------------------------------------------------------------------
# Cold-start benchmark: first-result latency with and without the
# persistent caches (artifact store + JAX compilation cache).
# ---------------------------------------------------------------------------

_COLDSTART_MARK = "COLDSTART_JSON:"


def _coldstart_workload(store_dir: str, t_spawn: float) -> None:
    """The child process body: one previously-declared sweep geometry,
    warmed up, captured, simulated.  Prints a JSON record tagged
    ``COLDSTART_JSON:`` for the parent."""
    import json
    import time

    from repro.api import Session
    from repro.core.features import num_extractions
    from repro.engine import xla_cache_counters

    from .common import TEST_LEN, tao_config

    t_session = time.time()
    sess = Session(tao_config(), store=store_dir)
    # declare the geometry set up front: sim step AND train step compile
    # (or, warm, deserialize) before any trace exists
    sess.warmup([TEST_LEN], train=True)
    model = sess.init_model(seed=7)
    tr = sess.capture("mcf", TEST_LEN)
    res = model.simulate(tr)
    first = time.time()
    rep = sess.sweep({"m": model}, {"t": tr})
    out = {
        # what the caches can address: Session construction -> first metric
        "cold_start_to_first_result_s": first - t_session,
        # process-inclusive variant (interpreter + jax import overhead
        # rides in both cold and warm, diluting the ratio)
        "spawn_to_first_result_s": first - t_spawn,
        "total_s": time.time() - t_spawn,
        "cpi": res.cpi,
        "l1d_mpki": res.l1d_mpki,
        "branch_mpki": res.branch_mpki,
        "xla": xla_cache_counters(),
        "features_extracted": num_extractions(),
        "sweep_features_extracted": rep.features_extracted,
        "sweep_features_from_store": rep.features_from_store,
        "store": sess.store.stats(),
    }
    print(_COLDSTART_MARK + json.dumps(out), flush=True)


def run_coldstart() -> None:
    """Run the identical workload in two fresh subprocesses against one
    store and one compile cache, both emptied first (under the checkout's
    git-ignored ``.cache/coldstart``): the first pays every cost (feature
    extraction, detailed sim, XLA), the second must hit the artifact store
    and deserialize every executable.  Emits before/after
    ``cold_start_to_first_result_s`` and stores the full records in the
    --json artifact (``coldstart`` key).

    The children need the device, and on a TPU host one process holds the
    chip: this refuses to run once the parent has initialised a JAX
    backend (``benchmarks.run`` schedules it before every other suite)."""
    import json
    import os
    import shutil
    import subprocess
    import sys
    import time

    from repro.compat import backend_initialized

    from .common import SCALE, emit, set_extra

    if backend_initialized():
        raise RuntimeError(
            "coldstart needs the device in its child processes, but this "
            "process already initialised a JAX backend; run it first or alone"
        )
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    root = os.path.join(repo, ".cache", "coldstart")
    shutil.rmtree(root, ignore_errors=True)
    store = os.path.join(root, "store")

    def child():
        env = dict(os.environ)
        env["PYTHONPATH"] = os.pathsep.join(
            [os.path.join(repo, "src"), env.get("PYTHONPATH", "")]
        ).rstrip(os.pathsep)
        env.setdefault("BENCH_SCALE", SCALE)
        env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(root, "jax")
        code = (
            "from benchmarks.bench_dse import _coldstart_workload; "
            f"_coldstart_workload({store!r}, {time.time()!r})"
        )
        p = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True, text=True, cwd=repo, env=env, timeout=1800,
        )
        if p.returncode != 0:
            raise RuntimeError(
                f"coldstart child failed:\n{p.stdout[-2000:]}\n{p.stderr[-4000:]}"
            )
        line = [
            ln for ln in p.stdout.splitlines() if ln.startswith(_COLDSTART_MARK)
        ][-1]
        return json.loads(line[len(_COLDSTART_MARK):])

    try:
        cold = child()
        warm = child()
    finally:
        shutil.rmtree(root, ignore_errors=True)

    # correctness first: the warm process must reproduce the cold one's
    # metrics bit-for-bit from cached artifacts
    for k in ("cpi", "l1d_mpki", "branch_mpki"):
        assert warm[k] == cold[k], (k, warm[k], cold[k])
    assert warm["xla"]["misses"] == 0, warm["xla"]
    assert warm["xla"]["requests"] > 0, warm["xla"]
    assert warm["features_extracted"] == 0, warm["features_extracted"]

    before = cold["cold_start_to_first_result_s"]
    after = warm["cold_start_to_first_result_s"]
    speedup = before / max(after, 1e-9)
    emit(
        "coldstart/cold", before * 1e6,
        f"first_result_s={before:.2f};xla_misses={cold['xla']['misses']};"
        f"extractions={cold['features_extracted']}",
    )
    emit(
        "coldstart/warm", after * 1e6,
        f"first_result_s={after:.2f};xla_misses={warm['xla']['misses']};"
        f"xla_hits={warm['xla']['hits']};extractions=0",
    )
    emit(
        "coldstart/speedup", 0.0,
        f"cold_start_to_first_result_s_before={before:.2f};"
        f"cold_start_to_first_result_s_after={after:.2f};"
        f"speedup={speedup:.1f}x;"
        f"spawn_to_first_before={cold['spawn_to_first_result_s']:.2f};"
        f"spawn_to_first_after={warm['spawn_to_first_result_s']:.2f}",
    )
    set_extra(
        "coldstart",
        {
            "cold_start_to_first_result_s_before": before,
            "cold_start_to_first_result_s_after": after,
            "speedup": speedup,
            "cold": cold,
            "warm": warm,
        },
    )
