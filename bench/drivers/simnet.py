"""SimNet simulation requests through ``TrainedModel.engine(...).simulate``.

Traffic parameters (``bench/traffic/<name>.json``):

  programs           the trace pool's programs
  pool_instructions  functional instructions captured per program
  lengths            {min, max, sizes}: the request lengths, log-spaced
  check_requests     requests compared with the reference after the
                     window (the longest completed one among them)
  limits             latency_gap: the largest gap, in cycles, between a
                     latency the program rounded and the reference's
                     rounding range (``bench/reference/simnet.py``);
                     cpi_gap and count_gap: 0

The configuration names the design point (``uarch``) whose detailed trace
gives SimNet its inputs (``bench/detailed.py``).  A request is one slice of
one program's rows, drawn as ``paper.sim-long`` draws its functional
slices (same pool, lengths and salt: under one seed both cells simulate
the same slices).  Every length is simulated once in set-up, so the window
compiles nothing.

The check is teacher-forced on the program's own trajectory: for each
compared request the reference rebuilds every context from the latencies
the timed program wrote and recomputes every prediction, at
``precision="highest"``.  ``latency_gap`` is the least tolerance at which
every rounding the program made lies in the reference's rounding range;
``cpi_gap`` the distance between the program's CPI and the CPI its own
latencies make; ``count_gap`` between its instruction count and the
slice's.  The control is the reference in fp8 (e4m3), read as the program
is.
"""
from __future__ import annotations

import gc
from typing import Dict, List

import numpy as np

from bench import detailed, pool
from bench.harness import derive_seed
from bench.reference import simnet as ref

# tiny widths for a rehearsal on the CPU (with the harness's window 33 and
# batch 16)
REHEARSE = {"channels": 16, "hidden": 16, "subtrace": 128}


class Driver:
    SPAN = "request"

    def __init__(self, run):
        self.run = run
        self.t = run.traffic
        self.w = {**run.config, **REHEARSE} if run.rehearse else dict(run.config)

    # ---- set-up --------------------------------------------------------

    def setup(self) -> None:
        from repro.core.simnet import SimNetConfig

        run, t, w = self.run, self.t, self.w
        self.cfg = SimNetConfig(window=w["window"], channels=w["channels"], n_conv=w["n_conv"],
                                kernel_size=w["kernel_size"], hidden=w["hidden"],
                                feat_dim=w["feat_dim"], subtrace=w["subtrace"])
        self.rows = detailed.rows(run, t["programs"], run.size(t["pool_instructions"]), w["uarch"])
        self.lengths = pool.length_set(t["lengths"], lambda n: run.size(n, 2 * w["window"]))
        self.reseed(run.seed)

    def weights(self) -> Dict:
        import jax

        key = jax.random.PRNGKey(derive_seed(self.run.seed, "weights"))
        return jax.jit(lambda k: ref.init_params(k, self.w))(key)

    def reseed(self, seed: int) -> None:
        """Weights and traffic of ``seed``; compiled programs are kept."""
        from repro.api import TrainedModel

        self.run.seed = seed
        self.release()
        self.done: List[Dict] = []
        self.params = self.weights()
        self.model = TrainedModel(params=self.params, cfg=self.cfg, name="simnet")
        self.engine = self.model.engine(batch_size=self.w["batch_size"])
        first = self.rows[sorted(self.rows)[0]]
        for n in self.lengths:
            self.engine.warmup(n)
            self.engine.simulate(first[:n])
        self.slices = pool.Slices(self.rows, self.lengths, self.run.rng("traffic"))

    # ---- the window ----------------------------------------------------

    def request(self, i: int) -> Dict:
        p, off, n = self.slices.next()
        r = self.engine.simulate(self.rows[p][off: off + n])
        self.done.append({"program": p, "offset": off, "length": n, "result": r})
        return {"instructions": r.num_instructions}

    def counters(self) -> List[str]:
        from repro.engine import cache_stats

        ctx = [d["result"].metrics["context_rows"] for d in self.done]
        return [f"engine cache_stats: {cache_stats()}",
                f"engine step traces: {self.engine.num_compiles}",
                f"request lengths: {self.lengths}",
                f"plans: {[self.engine.batch_rows(n) for n in self.lengths]}",
                f"mean in-flight context rows: {float(np.mean(ctx)) if ctx else None}"]

    def release(self) -> None:
        """Drop the program's state (engine, model); the window's results
        keep their latencies on the device for the check."""
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

    def slice(self, req: Dict) -> np.ndarray:
        return self.rows[req["program"]][req["offset"]: req["offset"] + req["length"]]

    def check(self, win) -> Dict[str, float]:
        chosen = self.sample()
        self.checked = []
        for req in chosen:
            r = req["result"]
            self.checked.append({"program": req["program"], "offset": req["offset"],
                                 "length": req["length"], "lat": r.latencies(), "cpi": r.cpi,
                                 "instructions": r.num_instructions})
        for req in self.done:  # the device arrays go before the reference runs
            req.pop("result", None)
        gc.collect()
        if not self.checked:
            return {}
        self.want = [ref.teacher(self.params, self.slice(c), c["lat"], self.w) for c in self.checked]
        gaps = [ref.latency_gap(wt["raw"], wt["lat"]) for wt in self.want]
        self.detail = {"latency_gap_per_request": gaps,
                       "lengths": [c["length"] for c in self.checked]}
        return {"latency_gap": max(gaps),
                "cpi_gap": max(abs(c["cpi"] - wt["cpi"]) for c, wt in zip(self.checked, self.want)),
                "count_gap": float(max(abs(c["instructions"] - c["length"]) for c in self.checked))}

    def control(self) -> Dict[str, float]:
        """The reference in fp8 (e4m3) on the same contexts, its roundings
        read against the full-precision reference's ranges."""
        gaps = []
        for c, wt in zip(self.checked, self.want):
            q = ref.teacher(self.params, self.slice(c), c["lat"], self.w, quant=True)
            gaps.append(ref.latency_gap(wt["raw"], ref.round_lat(q["raw"])))
        return {"latency_gap": max(gaps)}

    def faults(self) -> Dict[str, Dict[str, float]]:
        return {}
