"""Transfer training through ``TrainedModel.transfer`` (§4.3).

Traffic parameters:

  programs, instructions   the training traces (functional, per program)
  uarch                    the target design point (``repro.uarch``)
  batch_size, lr, epochs   the fine-tuning recipe (AdamW; embedding frozen)
  limits                   the largest gap each compared number may show

Data: the §4.1 adjusted, labelled traces of each program on the target
design point (the simulator's detailed model), made once per checkout;
each run builds the windowed dataset from them.  A base model made from
the seed is fine-tuned back to back, one epoch per call, each call's
shuffle drawn from the seed.

Set-up compiles the step, then makes one call whose first three steps are
recorded as they leave the compiled step (loss, optimizer state, weights);
the same step object then serves the window.  After the window the plain
reference follows those three steps on the same rows.
"""
from __future__ import annotations

import gc
import os
from typing import Dict, List

import numpy as np

from bench import weights
from bench.harness import derive_seed
from bench.reference import features as F
from bench.reference import model as ref

LABELS = {"fetch_lat": np.float32, "exec_lat": np.float32, "mispred": np.float32,
          "dlevel": np.int32, "icache_miss": np.float32, "tlb_miss": np.float32,
          "is_branch": np.float32, "is_mem": np.float32}
B1 = 0.9  # AdamW's first-moment decay (the optimizer's default)
CHECK_STEPS = 3


def adjusted(run, program: str, n: int, uarch: str) -> np.ndarray:
    """The labelled trace of ``program`` on ``uarch``, cached per checkout."""
    path = run.cache("train", f"{program}-{n}-{uarch}.npy")
    if os.path.exists(path):
        return np.load(path)
    import repro.uarch as U
    from repro.core.align import build_adjusted_trace

    prog = U.get_benchmark(program)
    det, _ = U.run_detailed(prog, U.run_functional(prog, n), getattr(U, uarch))
    adj = build_adjusted_trace(det).adjusted
    tmp = path + f".{os.getpid()}.tmp.npy"
    np.save(tmp, adj)
    os.replace(tmp, path)
    return adj


class Spy:
    """Records the first ``n`` calls of a compiled train step."""

    def __init__(self, fn, n: int):
        self.fn, self.n, self.calls = fn, n, []

    def __call__(self, params, opt, batch):
        out = self.fn(params, opt, batch)
        if len(self.calls) < self.n:
            self.calls.append({"opcode": batch["opcode"], "loss": out[2],
                               "mu": out[1].mu if not self.calls else None,
                               "params": out[0] if len(self.calls) == self.n - 1 else None})
        return out


class Driver:
    SPAN = "train_call"

    def __init__(self, run):
        self.run = run
        self.t = run.traffic
        self.w = run.config

    def setup(self) -> None:
        from repro.core.dataset import build_windows, concat_datasets
        from repro.core.features import extract_features
        from repro.core.transfer import warmup_train_step

        run, t = self.run, self.t
        self.cfg = run.tao_config()
        n = run.size(t["instructions"], 4 * self.w["window"])
        self.adj = [adjusted(run, p, n, t["uarch"]) for p in t["programs"]]
        self.dataset = concat_datasets([
            build_windows(extract_features(a, self.cfg.features), self.cfg.window, dedup=True)
            for a in self.adj
        ])
        self.entry = warmup_train_step(self.cfg, batch_size=t["batch_size"], lr=t["lr"],
                                       freeze_embed=True)
        self.reseed(run.seed)

    def reseed(self, seed: int) -> None:
        """Weights of ``seed``; one call through the compiled step with its
        first steps recorded."""
        from repro.api import TrainedModel

        self.run.seed = seed
        self.params = weights.single(self.run)
        self.base = TrainedModel(params=self.params, cfg=self.cfg, name="base")
        inner = self.entry.aot if self.entry.aot is not None else self.entry.fn
        spy = Spy(inner, CHECK_STEPS)
        if self.entry.aot is not None:
            self.entry.aot = spy
        else:
            self.entry.fn = spy
        try:
            self.call(0)
        finally:
            if self.entry.aot is spy:
                self.entry.aot = inner
            else:
                self.entry.fn = inner
        import jax

        self.recorded = jax.device_get(spy.calls)

    def shuffle_seed(self, i: int) -> int:
        return derive_seed(self.run.seed, "shuffle", i)

    def call(self, i: int):
        t = self.t
        return self.base.transfer(self.dataset, freeze_embed=True, epochs=t["epochs"],
                                  batch_size=t["batch_size"], lr=t["lr"],
                                  seed=self.shuffle_seed(i))

    def request(self, i: int) -> Dict:
        m = self.call(i + 1)
        return {"windows": m.steps * self.t["batch_size"]}

    def counters(self) -> List[str]:
        from repro.train.trainer import cache_stats, train_step_compiles

        return [f"train cache_stats: {cache_stats()}",
                f"train-step traces: {train_step_compiles()}",
                f"dataset windows: {len(self.dataset)} "
                f"({len(self.dataset) // self.t['batch_size']} steps per call)"]

    def release(self) -> None:
        """Drop the program's device state (the base model)."""
        self.__dict__.pop("base", None)
        gc.collect()

    # ---- the reference ---------------------------------------------------

    def ref_rows(self) -> Dict:
        """The windowed dataset, from the reference's own features: windows
        of ``window`` rows, duplicates (equal features and latencies)
        dropped per trace, traces concatenated."""
        W = self.w["window"]
        parts = []
        for a in self.adj:
            x = {k: F.windows(v, W) for k, v in F.features(a, self.w).items()}
            x["labels"] = {k: F.windows(a[k].astype(dt), W) for k, dt in LABELS.items()}
            key = np.concatenate([x["opcode"].reshape(len(x["opcode"]), -1).view(np.uint8),
                                  x["memdist"].reshape(len(x["opcode"]), -1).view(np.uint8),
                                  x["brhist"].reshape(len(x["opcode"]), -1).view(np.uint8),
                                  x["labels"]["fetch_lat"].view(np.uint8),
                                  x["labels"]["exec_lat"].view(np.uint8)], axis=1)
            first: Dict[bytes, int] = {}
            for i, row in enumerate(key):
                first.setdefault(row.tobytes(), i)
            keep = np.array(sorted(first.values()))
            parts.append({k: (v[keep] if k != "labels" else {m: u[keep] for m, u in v.items()})
                          for k, v in x.items()})
        out = {k: np.concatenate([p[k] for p in parts]) for k in parts[0] if k != "labels"}
        out["labels"] = {m: np.concatenate([p["labels"][m] for p in parts]) for m in LABELS}
        return out

    def ref_steps(self, quant: bool = False, rows: int = 0, frozen: bool = False) -> Dict:
        """The reference's first steps on the rows the program's feed drew
        (``quant``: the fp8 control; planted faults: ``rows``, keep only that
        many rows of each batch; ``frozen``, a step that returns its state
        unchanged)."""
        import jax
        import jax.numpy as jnp

        data = self.rows
        n = len(data["opcode"])
        bs = self.t["batch_size"]
        order = np.arange(n)
        np.random.default_rng(self.shuffle_seed(0)).shuffle(order)
        w, lr = self.w, self.t["lr"]

        @jax.jit
        def step(p, st, b):
            loss, g = ref.head_grad(p, b, w, quant=quant)
            g = ref.clip(g)
            head, new = ref.adamw({"adapt": p["adapt"], "pred": p["pred"]}, g, st, lr=lr)
            if frozen:
                return p, st, loss, g
            return {"embed": p["embed"], **head}, new, loss, g

        p = self.params
        head = {"adapt": p["adapt"], "pred": p["pred"]}
        zeros = jax.tree.map(jnp.zeros_like, head)
        st = (jnp.zeros((), jnp.float32), zeros, zeros)
        out = {"loss": [], "opcode": []}
        for k in range(CHECK_STEPS):
            idx = order[k * bs: (k + 1) * bs]
            if rows:
                idx = idx[:rows]
            b = jax.tree.map(lambda v: v[idx], {x: data[x] for x in data})
            p, st, loss, g = step(p, st, b)
            out["loss"].append(float(loss))
            out["opcode"].append(b["opcode"])
            if k == 0:
                out["grad"] = jax.device_get(g)
        out["params"] = jax.device_get(p)
        return out

    def gaps(self, got: Dict, want: Dict) -> Dict[str, float]:
        """``loss_rel_gap``: the widest relative gap of the steps' losses.
        Per leaf of adapt+pred, the gap between the program's and the
        reference's norm of the first gradient (``grad_norm_gap``) and of
        the weights' change after the steps (``update_norm_gap``), over the
        larger of the reference's norm of that leaf and the median leaf's;
        the worst leaf counts.  The change leaves out every element whose
        reference gradient is under a thousandth of the median leaf's root
        mean square: such an element (a key's bias under softmax) moves
        under Adam by round-off alone.  ``self.detail``: each step's loss
        gap, the worst leaves, the elements left out."""
        import jax

        rel = [abs(a - b) / abs(b) for a, b in zip(got["loss"], want["loss"])]
        paths = [jax.tree_util.keystr(k) for k, _ in jax.tree_util.tree_leaves_with_path(want["grad"])]
        g_p = [np.asarray(x, np.float64) for x in jax.tree.leaves(got["grad"])]
        g_r = [np.asarray(x, np.float64) for x in jax.tree.leaves(want["grad"])]
        p0 = jax.tree.leaves(jax.device_get({"adapt": self.params["adapt"], "pred": self.params["pred"]}))
        head = lambda t: jax.tree.leaves({"adapt": t["adapt"], "pred": t["pred"]})  # noqa: E731
        floor = 1e-3 * np.median([np.sqrt(np.mean(g * g)) for g in g_r])
        keep = [np.abs(g) >= floor for g in g_r]
        d_p = [np.linalg.norm((np.asarray(a, np.float64) - b)[k]) for a, b, k in zip(head(got["params"]), p0, keep)]
        d_r = [np.linalg.norm((np.asarray(a, np.float64) - b)[k]) for a, b, k in zip(head(want["params"]), p0, keep)]
        gp, gr = [np.linalg.norm(x) for x in g_p], [np.linalg.norm(x) for x in g_r]
        gmed, dmed = np.median(gr), np.median(d_r)
        grad = [abs(a - b) / max(b, gmed) for a, b in zip(gp, gr)]
        upd = [abs(a - b) / max(b, dmed) for a, b in zip(d_p, d_r)]
        self.detail = {"loss_step_gaps": rel, "grad_worst": paths[int(np.argmax(grad))],
                       "update_worst": paths[int(np.argmax(upd))],
                       "elements_left_out": int(sum((~k).sum() for k in keep))}
        return {"loss_rel_gap": float(max(rel)), "grad_norm_gap": float(max(grad)),
                "update_norm_gap": float(max(upd))}

    def program_steps(self) -> Dict:
        import jax

        rec = self.recorded
        return {"loss": [float(r["loss"]) for r in rec],
                "grad": jax.tree.map(lambda v: v / (1 - B1), rec[0]["mu"]),
                "params": rec[-1]["params"], "opcode": [r["opcode"] for r in rec]}

    def check(self, win) -> Dict[str, float]:
        self.rows = self.ref_rows()
        self.want = self.ref_steps()
        got = self.program_steps()
        differ = sum(int(not np.array_equal(a, b)) for a, b in zip(got["opcode"], self.want["opcode"]))
        return {"batch_rows_differ": differ, **self.gaps(got, self.want)}

    def control(self) -> Dict[str, float]:
        """The reference with its dense layers as fp8 (e4m3) matmuls, forward
        and backward: the precision below the configuration's one-pass
        bfloat16 matmuls."""
        return self.gaps(self.ref_steps(quant=True), self.want)

    def faults(self) -> Dict[str, Dict[str, float]]:
        half = self.ref_steps(rows=self.t["batch_size"] // 2)
        return {"half_batch": self.gaps(half, self.want),
                "unchanged": self.gaps(self.ref_steps(frozen=True), self.want)}
