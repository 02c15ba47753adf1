"""Functional-trace pool: each program captured once per checkout.

A trace is the simulator's µarch-agnostic functional trace (its
``run_functional``), the input every simulation request slices.  Captures
are a pure function of (program, length) and are kept as ``.npy`` under the
checkout's git-ignored ``.cache/bench``, so only a cell's first run in a
checkout pays for them.
"""
from __future__ import annotations

import os
from typing import Dict, List

import numpy as np


def functional(run, program: str, n: int) -> np.ndarray:
    path = run.cache("pool", f"{program}-{n}.npy")
    if os.path.exists(path):
        return np.load(path)
    from repro.uarch import get_benchmark, run_functional

    trace = run_functional(get_benchmark(program), n)
    tmp = path + f".{os.getpid()}.tmp.npy"
    np.save(tmp, trace)
    os.replace(tmp, path)
    return trace


def load(run, programs: List[str], n: int) -> Dict[str, np.ndarray]:
    return {p: functional(run, p, n) for p in programs}


def length_set(spec: Dict, size) -> List[int]:
    """The request lengths a cell draws from: ``sizes`` values spaced
    log-uniformly over [min, max].  Every seed gets the same set (so the
    same shapes to warm and the same work), in its own order."""
    lo, hi = size(spec["min"]), size(spec["max"])
    return sorted({int(round(x)) for x in np.geomspace(lo, hi, spec["sizes"])})


class Slices:
    """Request slices of the pool, drawn from the seed: the length cycles
    through seed-ordered permutations of the length set; program and offset
    are drawn uniformly; no (program, offset, length) repeats in a run."""

    def __init__(self, pool: Dict[str, np.ndarray], lengths: List[int], rng):
        self.pool = pool
        self.names = sorted(pool)
        self.lengths = lengths
        self.rng = rng
        self.order: List[int] = []
        self.seen = set()

    def next(self):
        if not self.order:
            self.order = list(self.rng.permutation(self.lengths))
        n = int(self.order.pop())
        while True:
            p = self.names[int(self.rng.integers(len(self.names)))]
            off = int(self.rng.integers(0, len(self.pool[p]) - n + 1))
            if (p, off, n) not in self.seen:
                self.seen.add((p, off, n))
                return p, off, n
