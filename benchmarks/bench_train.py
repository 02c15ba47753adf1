"""Training-path streaming benchmarks (ROADMAP "Training-path streaming").

Measures the streaming training pipeline (`StreamingWindowDataset` +
process-wide cached train step) against the materialized `build_windows`
path on a synthetic trace:

  training/stream_windows_per_s        streaming data path + 1 train epoch
  training/materialized_windows_per_s  materialized path, same model/seed
  training/speedup                     stream / materialized
  training/peak_rss_stream_mb          peak RSS *delta* of the data path +
  training/peak_rss_materialized_mb      epoch, measured in a subprocess
                                         over a post-FeatureSet baseline
  training/rss_ratio                   materialized / stream (the ISSUE's
                                         >= 5x target at 1M instructions)
  training/train_compiles              train-step traces in the streaming
                                         subprocess (== 1 per geometry)
  training/loss_bitwise_equal          streaming loss trajectory is
                                         bit-identical to materialized
  training/dedup_hash_chunked          chunked window digesting vs the old
                                         per-row loop (same digests)

RSS runs happen in subprocesses (`python -m benchmarks.bench_train
--measure stream|materialized`) so each path's peak is attributed cleanly;
the subprocess pins ``JAX_PLATFORMS=cpu``.  CI uploads the rows as
``BENCH_train.json`` (suite name: ``training``).
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import resource
import subprocess
import sys
import time

import numpy as np

from repro.core import FeatureConfig, TaoConfig  # noqa: F401 (typing/docs)
from repro.core.dataset import (
    StreamingWindowDataset,
    build_windows,
    iter_window_digests,
    window_view,
)
from repro.core.features import NUM_OPCODES, FeatureSet
from repro.core.transfer import train_tao_impl
from repro.train.trainer import train_step_compiles
from repro.uarch.isa import NUM_REGS

from .common import FEATURES, SCALE, Timer, emit, tao_config

# instruction counts: EQ_N feeds the in-process bit-for-bit/compile checks,
# RSS_N the subprocess memory/throughput comparison (1M at full scale — the
# acceptance target)
EQ_N = {"tiny": 30_000, "small": 80_000, "full": 150_000}
RSS_N = {"tiny": 150_000, "small": 400_000, "full": 1_000_000}

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def synthetic_features(
    n: int,
    fcfg: FeatureConfig,
    *,
    seed: int = 0,
    window: int = 0,
    dup_every: int = 0,
) -> FeatureSet:
    """A random labeled FeatureSet of ``n`` instructions (no detailed sim —
    trace-scale inputs in milliseconds).  With ``dup_every`` > 0 every
    ``dup_every``-th window-aligned block repeats block 0, so the dedup
    paths have real collisions to resolve."""
    rng = np.random.default_rng(seed)
    # float32 draws throughout: float64 temporaries at 1M instructions would
    # dwarf the data-path allocations the RSS benchmark isolates
    fs = FeatureSet(
        opcode=rng.integers(0, NUM_OPCODES, n).astype(np.int32),
        regbits=(rng.random((n, NUM_REGS), dtype=np.float32) < 0.1).astype(np.float32),
        flags=(rng.random((n, 5), dtype=np.float32) < 0.3).astype(np.float32),
        brhist=rng.integers(-1, 2, (n, fcfg.n_queue)).astype(np.float32),
        memdist=rng.standard_normal((n, fcfg.n_mem), dtype=np.float32),
        labels={
            "fetch_lat": rng.integers(0, 8, n).astype(np.float32),
            "exec_lat": rng.integers(1, 12, n).astype(np.float32),
            "mispred": (rng.random(n) < 0.1).astype(np.float32),
            "dlevel": rng.integers(0, 4, n).astype(np.int32),
            "icache_miss": (rng.random(n) < 0.05).astype(np.float32),
            "tlb_miss": (rng.random(n) < 0.02).astype(np.float32),
            "is_branch": (rng.random(n) < 0.2).astype(np.float32),
            "is_mem": (rng.random(n) < 0.3).astype(np.float32),
        },
    )
    if dup_every and window:
        for k in range(dup_every, n // window, dup_every):
            lo = k * window
            for arr in (fs.opcode, fs.regbits, fs.flags, fs.brhist, fs.memdist,
                        *fs.labels.values()):
                arr[lo : lo + window] = arr[:window]
    return fs


def _rss_now_bytes() -> int:
    try:  # Linux: current resident set from /proc (page counts)
        with open("/proc/self/statm") as f:
            return int(f.read().split()[1]) * os.sysconf("SC_PAGESIZE")
    except (OSError, ValueError, IndexError):
        kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        return kb * (1 if sys.platform == "darwin" else 1024)


class _RssPeak:
    """Peak resident-set size over a region, via a 1 ms sampling thread.

    ``ru_maxrss`` is process-lifetime-monotonic: allocation spikes during
    setup (feature generation, XLA compilation) would mask the data path's
    own peak.  Sampling the *current* RSS bounds the measurement to the
    region of interest."""

    def __enter__(self):
        import threading

        self.peak = _rss_now_bytes()
        self._stop = threading.Event()

        def sample():
            while not self._stop.is_set():
                self.peak = max(self.peak, _rss_now_bytes())
                self._stop.wait(0.001)

        self._t = threading.Thread(target=sample, daemon=True)
        self._t.start()
        return self

    def __exit__(self, *a):
        self._stop.set()
        self._t.join()
        self.peak = max(self.peak, _rss_now_bytes())


def _measure(mode: str, n: int) -> dict:
    """Subprocess body: peak-RSS delta + throughput of one data path.

    The FeatureSet (O(trace), common to both paths) and the train-step
    compile are built BEFORE the RSS baseline, so the delta isolates what
    this PR changes: windowing, dedup, shuffling, and batch materialization
    (plus the per-batch jax buffers, identical in both modes)."""
    cfg = tao_config()
    fs = synthetic_features(n, FEATURES, seed=1, window=cfg.window, dup_every=7)
    warm = StreamingWindowDataset(fs.slice(0, cfg.window * 64), cfg.window)
    train_tao_impl(cfg, warm, epochs=1, batch_size=16, seed=0)
    import gc

    gc.collect()
    base = _rss_now_bytes()

    with _RssPeak() as rss:
        t0 = time.perf_counter()
        if mode == "stream":
            ds = StreamingWindowDataset(fs, cfg.window)
        else:
            ds = build_windows(fs, cfg.window)
        build_secs = time.perf_counter() - t0
        c0 = train_step_compiles()
        t1 = time.perf_counter()
        res = train_tao_impl(cfg, ds, epochs=1, batch_size=16, seed=0)
        train_secs = time.perf_counter() - t1
    return {
        "mode": mode,
        "n": n,
        "windows": len(ds),
        "peak_rss_delta_mb": (rss.peak - base) / 1e6,
        "build_seconds": build_secs,
        "train_seconds": train_secs,
        "windows_per_s": res.steps * 16 / (build_secs + train_secs),
        "compiles_during_train": train_step_compiles() - c0,
        "train_compiles_total": train_step_compiles(),
        "loss0": res.losses[0],
    }


def _spawn_measure(mode: str, n: int) -> dict:
    env = dict(os.environ)
    # Off the chip by design: the child measures the host data pipeline's
    # peak RSS, and the parent (benchmarks.run) already holds the device.
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(_ROOT, "src")]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else [])
    )
    p = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_train",
         "--measure", mode, "--n", str(n)],
        capture_output=True, text=True, timeout=3600, env=env, cwd=_ROOT,
    )
    if p.returncode != 0:
        raise RuntimeError(f"measure {mode} failed:\n{p.stderr[-3000:]}")
    return json.loads(p.stdout.strip().splitlines()[-1])


def _per_row_digests(inputs, labels):
    """The pre-vectorization per-row hashing loop (kept here as the
    baseline the chunked implementation is benchmarked against)."""
    out = []
    lat = labels["fetch_lat"] if labels is not None else None
    for i in range(len(inputs["opcode"])):
        h = hashlib.blake2b(digest_size=16)
        h.update(inputs["opcode"][i].tobytes())
        h.update(inputs["memdist"][i].tobytes())
        h.update(inputs["brhist"][i].tobytes())
        if lat is not None:
            h.update(lat[i].tobytes())
            h.update(labels["exec_lat"][i].tobytes())
        out.append(h.digest())
    return out


def run() -> None:
    cfg = tao_config()
    n = EQ_N[SCALE]
    fs = synthetic_features(n, FEATURES, seed=0, window=cfg.window, dup_every=5)

    # --- bit-for-bit: streaming vs materialized loss trajectory ---------
    ds_s = StreamingWindowDataset(fs, cfg.window)
    ds_m = build_windows(fs, cfg.window)
    c0 = train_step_compiles()
    res_s = train_tao_impl(cfg, ds_s, epochs=2, batch_size=16, seed=0)
    compiles = train_step_compiles() - c0
    res_m = train_tao_impl(cfg, ds_m, epochs=2, batch_size=16, seed=0)
    equal = int(res_s.losses == res_m.losses and len(ds_s) == len(ds_m))
    emit(
        "training/loss_bitwise_equal",
        0.0,
        f"equal={equal} windows={len(ds_s)} dropped={ds_s.num_dropped}",
    )
    emit(
        "training/train_compiles",
        0.0,
        f"compiles={compiles} (streaming epochs=2; 1 per geometry)",
    )

    # --- chunked vs per-row window hashing (same digests) ---------------
    dense = {  # stride-1 views: one window per trace position, zero copies
        k: window_view(getattr(fs, k), cfg.window, 1)
        for k in ("opcode", "memdist", "brhist")
    }
    labs = {  # training dedup hashes labels too — the realistic case
        k: window_view(fs.labels[k], cfg.window, 1)
        for k in ("fetch_lat", "exec_lat")
    }
    with Timer() as t_chunk:
        chunked = list(iter_window_digests(dense, labs))
    with Timer() as t_row:
        per_row = _per_row_digests(dense, labs)
    assert chunked == per_row
    emit(
        "training/dedup_hash_chunked",
        t_chunk.seconds * 1e6 / len(chunked),
        f"windows={len(chunked)} speedup={t_row.seconds / t_chunk.seconds:.2f}x"
        " (blake2b compression is the remaining floor)",
    )

    # --- subprocess peak-RSS + throughput comparison --------------------
    rss_n = RSS_N[SCALE]
    stream = _spawn_measure("stream", rss_n)
    mat = _spawn_measure("materialized", rss_n)
    assert stream["loss0"] == mat["loss0"]  # same keep-set, same first epoch
    emit(
        "training/stream_windows_per_s",
        1e6 / max(stream["windows_per_s"], 1e-9),
        f"windows_per_s={stream['windows_per_s']:.0f} n={rss_n}",
    )
    emit(
        "training/materialized_windows_per_s",
        1e6 / max(mat["windows_per_s"], 1e-9),
        f"windows_per_s={mat['windows_per_s']:.0f} n={rss_n}",
    )
    emit(
        "training/speedup",
        0.0,
        f"stream_vs_materialized={stream['windows_per_s'] / mat['windows_per_s']:.2f}x",
    )
    emit(
        "training/peak_rss_stream_mb",
        0.0,
        f"mb={stream['peak_rss_delta_mb']:.1f} n={rss_n} "
        f"compiles_during_train={stream['compiles_during_train']} "
        f"total={stream['train_compiles_total']}",
    )
    emit(
        "training/peak_rss_materialized_mb",
        0.0,
        f"mb={mat['peak_rss_delta_mb']:.1f} n={rss_n}",
    )
    ratio = mat["peak_rss_delta_mb"] / max(stream["peak_rss_delta_mb"], 1e-9)
    emit("training/rss_ratio", 0.0, f"materialized_vs_stream={ratio:.1f}x")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--measure", choices=("stream", "materialized"))
    ap.add_argument("--n", type=int, default=None)
    args = ap.parse_args()
    if args.measure:
        print(json.dumps(_measure(args.measure, args.n or RSS_N[SCALE])))
    else:
        run()
