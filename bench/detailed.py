"""Detailed-trace rows of the trace pool: SimNet's inputs.

Each program's functional trace (``bench/pool.py``) is run through the
detailed simulator on one design point and aligned (§4.1,
``build_adjusted_trace``): one row per committed instruction, so the rows
[off, off + n) are the functional slice [off, off + n) with the design
point's events beside it.  Rows are kept as ``.npy`` under the checkout's
git-ignored ``.cache/bench``, so only a cell's first run in a checkout pays
for them; that run captures the missing programs in parallel processes
(the detailed simulator is a Python loop: 1.5–3.8 s per 100k instructions
on one CPU core, by program).
"""
from __future__ import annotations

import concurrent.futures as cf
import multiprocessing
import os
from typing import Dict, List

import numpy as np

from bench import pool


def capture(func_path: str, program: str, uarch: str, out_path: str) -> None:
    """One program's rows, written to ``out_path`` (runs in a worker)."""
    os.environ["JAX_PLATFORMS"] = "cpu"  # a worker never touches the chip
    import repro.uarch as U
    from repro.core.align import build_adjusted_trace

    det, _ = U.run_detailed(U.get_benchmark(program), np.load(func_path), getattr(U, uarch))
    tmp = out_path + f".{os.getpid()}.tmp.npy"
    np.save(tmp, build_adjusted_trace(det).adjusted)
    os.replace(tmp, out_path)


def rows(run, programs: List[str], n: int, uarch: str) -> Dict[str, np.ndarray]:
    """Each program's ``n`` rows on ``uarch``: captured where missing (a
    rehearsal in this process, else one process per program), then loaded."""
    pool.load(run, programs, n)
    jobs = []
    for p in programs:
        out = run.cache("detailed", f"{p}-{n}-{uarch}.npy")
        if not os.path.exists(out):
            jobs.append((run.cache("pool", f"{p}-{n}.npy"), p, uarch, out))
    if jobs and run.rehearse:
        for job in jobs:
            capture(*job)
    elif jobs:
        ctx = multiprocessing.get_context("spawn")
        with cf.ProcessPoolExecutor(min(len(jobs), os.cpu_count() or 1), mp_context=ctx) as ex:
            for f in [ex.submit(capture, *job) for job in jobs]:
                f.result()
    return {p: np.load(run.cache("detailed", f"{p}-{n}-{uarch}.npy")) for p in programs}
