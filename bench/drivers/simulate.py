"""Simulation requests through ``TrainedModel.engine(...).simulate``.

Traffic parameters (``bench/traffic/<name>.json``):

  programs           the trace pool's programs
  pool_instructions  functional instructions captured per program
  lengths            {min, max, sizes}: the request lengths, log-spaced
  feature_backend    the engine's feature path
  check_requests     requests compared with the reference after the
                     window (the longest completed one among them)
  limits             the largest logit gap each head may need
                     (``bench/reference/model.py`` ``decision_gaps``)

A request is one contiguous slice of one program's trace; every length of
the set is simulated once in set-up, so the window compiles nothing.
"""
from __future__ import annotations

import gc
from typing import Dict, List

from bench import pool, weights
from bench.reference import model as ref

METRICS = ("cpi", "branch_mpki", "l1d_mpki")


def gaps(got: List[Dict], want: List[Dict]) -> Dict[str, float]:
    """``logit_gap``: the widest, over the compared requests and the three
    heads, of the logit gap that the program's metrics need
    (``ref.decision_gaps``); then each head's own widest."""
    per = [ref.decision_gaps(g, r) for g, r in zip(got, want)]
    heads = {k: max(p[k] for p in per) for k in per[0]}
    return {"logit_gap": max(heads.values()), **heads}


def int8(params, cfg, w, t, trace) -> Dict:
    """One request through the engine with ``precision="int8"``."""
    from repro.api import TrainedModel

    eng = TrainedModel(params=params, cfg=cfg).engine(
        batch_size=w["batch_size"], feature_backend=t["feature_backend"], precision="int8")
    r = eng.simulate(trace)
    return {m: float(r.metrics[m]) for m in METRICS}


class Driver:
    SPAN = "request"

    def __init__(self, run):
        self.run = run
        self.t = run.traffic
        self.w = run.config

    # ---- set-up --------------------------------------------------------

    def setup(self) -> None:
        run = self.run
        self.cfg = run.tao_config()
        self.pool = pool.load(run, self.t["programs"], run.size(self.t["pool_instructions"]))
        self.lengths = pool.length_set(self.t["lengths"], lambda n: run.size(n, 2 * self.w["window"]))
        self.reseed(run.seed)

    def reseed(self, seed: int) -> None:
        """Weights and traffic of ``seed``; compiled programs are kept."""
        from repro.api import TrainedModel

        self.run.seed = seed
        self.release()
        self.params = weights.single(self.run)
        self.model = TrainedModel(params=self.params, cfg=self.cfg, name="bench")
        self.engine = self.model.engine(batch_size=self.w["batch_size"],
                                        feature_backend=self.t["feature_backend"])
        first = self.pool[sorted(self.pool)[0]]
        for n in self.lengths:
            self.engine.warmup(n)
            self.engine.simulate(first[:n])
        self.slices = pool.Slices(self.pool, self.lengths, self.run.rng("traffic"))
        self.done: List[Dict] = []

    # ---- the window ----------------------------------------------------

    def request(self, i: int) -> Dict:
        p, off, n = self.slices.next()
        r = self.engine.simulate(self.pool[p][off: off + n])
        self.done.append({"program": p, "offset": off, "length": n,
                          **{m: float(r.metrics[m]) for m in METRICS}})
        return {"instructions": r.num_instructions}

    def counters(self) -> List[str]:
        from repro.engine import cache_stats

        return [f"engine cache_stats: {cache_stats()}",
                f"engine step traces: {self.engine.num_compiles}",
                f"request lengths: {self.lengths}"]

    def release(self) -> None:
        """Drop the program's state (engine, model)."""
        for k in ("engine", "model"):
            self.__dict__.pop(k, None)
        gc.collect()

    # ---- the check -----------------------------------------------------

    def sample(self) -> List[Dict]:
        """Requests to compare: the longest completed one, and the rest
        drawn from the seed."""
        k = self.t["check_requests"]
        if not self.done:
            return []
        longest = max(range(len(self.done)), key=lambda i: self.done[i]["length"])
        rest = [i for i in range(len(self.done)) if i != longest]
        pick = self.run.rng("check").choice(rest, size=min(k - 1, len(rest)), replace=False)
        return [self.done[i] for i in [longest, *sorted(int(x) for x in pick)]]

    def slice(self, req: Dict):
        return self.pool[req["program"]][req["offset"]: req["offset"] + req["length"]]

    def reference(self, req: Dict) -> Dict:
        return ref.simulate(self.params, self.slice(req), self.w)

    gaps = staticmethod(gaps)

    def check(self, win) -> Dict[str, float]:
        self.checked = self.sample()
        if not self.checked:
            return {}
        self.want = [self.reference(r) for r in self.checked]
        return self.gaps(self.checked, self.want)

    def control(self) -> Dict[str, float]:
        """The program's own int8 path (W8A8), the precision below the
        configuration's one-pass bfloat16 matmuls, on the same requests."""
        return self.gaps([int8(self.params, self.cfg, self.w, self.t, self.slice(r))
                          for r in self.checked], self.want)

    def faults(self) -> Dict[str, Dict[str, float]]:
        return {}

