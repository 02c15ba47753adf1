"""Design-space sweeps through ``Session.sweep`` under a data plan.

Traffic parameters:

  programs, pool_instructions   the trace pool (shared with the simulation
                                cells)
  traces_per_call, length       each call sweeps every design point over
                                this many slices of this length, from
                                distinct programs drawn from the seed
  feature_backend               the engine's feature path
  check_jobs                    (design point, slice) jobs compared with the
                                reference after the window
  limits                        the largest gap each compared number may show

The configuration gives the design points (``DesignSpace.sample``), each
with its own adapt+pred over one shared embedding, and the plan (mesh
shape and axes); the models are placed replicated over the plan's mesh in
set-up, as data-parallel state is.
"""
from __future__ import annotations

import gc
from typing import Dict, List

from bench import pool, weights
from bench.drivers.simulate import METRICS, gaps, int8
from bench.harness import derive_seed
from bench.reference import model as ref


class Driver:
    SPAN = "sweep_call"

    def __init__(self, run):
        self.run = run
        self.t = run.traffic
        self.w = run.config

    def setup(self) -> None:
        from repro.api import Session
        from repro.compat import make_mesh
        from repro.engine import ExecutionPlan

        run, t, w = self.run, self.t, self.w
        self.cfg = run.tao_config()
        self.pool = pool.load(run, t["programs"], run.size(t["pool_instructions"]))
        self.length = run.size(t["length"], 2 * w["window"])
        mesh = make_mesh(tuple(w["plan"]["mesh"]), tuple(w["plan"]["axes"]))
        self.plan = ExecutionPlan.resolve(mesh, batch_size=w["batch_size"])
        self.session = Session(self.cfg, batch_size=w["batch_size"],
                               feature_backend=t["feature_backend"], plan=self.plan)
        self.reseed(run.seed)

    def reseed(self, seed: int) -> None:
        from repro.api import DesignSpace, TrainedModel

        self.run.seed = seed
        self.release()
        count = self.w["design_points"] if not self.run.rehearse else min(self.w["design_points"], 3)
        designs = DesignSpace.sample(count, seed=derive_seed(seed, "designs"))
        embed, heads = weights.shared(self.run, count)
        self.models = {
            d.name: TrainedModel(
                params=self.plan.replicate({"embed": embed, **h}), cfg=self.cfg, name=d.name,
                uarch=d, sim_batch_size=self.w["batch_size"],
                sim_feature_backend=self.t["feature_backend"], sim_plan=self.plan)
            for d, h in zip(designs, heads)
        }
        del embed, heads
        self.rng = self.run.rng("traffic")
        self.seen = set()
        self.done: List[Dict] = []
        self.session.sweep(self.models, self.traces())  # warm every shape

    def traces(self) -> Dict:
        from repro.api import Trace
        from repro.uarch import get_benchmark

        names = sorted(self.pool)
        out = {}
        progs = self.rng.choice(len(names), size=self.t["traces_per_call"], replace=False)
        for j in progs:
            p = names[int(j)]
            while True:
                off = int(self.rng.integers(0, len(self.pool[p]) - self.length + 1))
                if (p, off) not in self.seen:
                    break
            self.seen.add((p, off))
            out[f"{p}@{off}"] = Trace(name=f"{p}@{off}", functional=self.pool[p][off: off + self.length],
                                      program=get_benchmark(p), benchmark=p)
        return out

    def request(self, i: int) -> Dict:
        rep = self.session.sweep(self.models, self.traces())
        self.last = rep
        for key, r in rep.results.items():
            model, trace = key.split("/")
            p, off = trace.split("@")
            self.done.append({"model": model, "program": p, "offset": int(off),
                              "length": self.length, **{m: float(r.metrics[m]) for m in METRICS}})
        return {"instructions": rep.num_instructions}

    def counters(self) -> List[str]:
        from repro.engine import cache_stats

        rep = getattr(self, "last", None)
        out = [f"engine cache_stats: {cache_stats()}", f"plan: {self.plan.describe()}"]
        if rep is not None:
            out.append(f"last sweep: {rep.stats()}")
        return out

    def release(self) -> None:
        self.__dict__.pop("models", None)
        gc.collect()

    # ---- the check -----------------------------------------------------

    def check(self, win) -> Dict[str, float]:
        if not self.done:
            return {}
        k = min(self.t["check_jobs"], len(self.done))
        pick = sorted(int(i) for i in self.run.rng("check").choice(len(self.done), size=k, replace=False))
        self.checked = [self.done[i] for i in pick]
        self.heads = self.ref_params()
        self.want = [self.reference(r) for r in self.checked]
        return self.gaps(self.checked, self.want)

    def ref_params(self) -> Dict:
        """The same weights again from the seed, on one device."""
        count = self.w["design_points"] if not self.run.rehearse else min(self.w["design_points"], 3)
        from repro.api import DesignSpace

        designs = DesignSpace.sample(count, seed=derive_seed(self.run.seed, "designs"))
        embed, heads = weights.shared(self.run, count)
        return {d.name: {"embed": embed, **h} for d, h in zip(designs, heads)}

    def slice(self, job: Dict):
        return self.pool[job["program"]][job["offset"]: job["offset"] + job["length"]]

    def reference(self, job: Dict) -> Dict:
        return ref.simulate(self.heads[job["model"]], self.slice(job), self.w)

    gaps = staticmethod(gaps)

    def control(self) -> Dict[str, float]:
        """The program's own int8 path (one device) on the same jobs."""
        return self.gaps([int8(self.heads[r["model"]], self.cfg, self.w, self.t, self.slice(r))
                          for r in self.checked], self.want)

    def faults(self) -> Dict[str, Dict[str, float]]:
        return {}
