"""The sequential step: SimNet's simulation on the streaming engine.

Tao predicts every window from the functional trace alone, so its step
maps independent windows to metrics.  SimNet (``core/simnet.py``) predicts
each instruction from the instructions still in flight, whose timing comes
from its own earlier predictions: instruction i+1 waits for instruction i.
Its step is therefore a ``lax.scan`` over instructions in which every
iteration advances every row by one instruction, and a row is a sub-trace:

  * **Sub-traces.**  A request of n detailed-trace rows is cut into
    ceil(n / ``subtrace``) sub-traces.  Sub-trace k counts instructions
    [k * subtrace, (k + 1) * subtrace) and first runs the ``warmup`` (the
    ROB's depth) before them, uncounted, so its context can fill; the first
    sub-trace has nothing before it and its warm-up iterations are masked.
    Every launch runs warmup + subtrace iterations.
  * **The plan.**  ``batch_rows`` gives the rows of each launch: full
    launches of ``batch_size`` sub-traces, then the rest on the fewest
    sixteenths of a batch that hold it, so an engine compiles at most
    sixteen geometries and pads under a sixteenth of a batch per request.
    Launches share nothing, so they are dispatched back to back.
  * **The carry** (per row): the newest ``context`` instructions' packed
    words and their fetch, completion and retire cycles (newest first; an
    empty slot retires at -1), the clock (the last fetch), the last retire,
    the clock at the first counted instruction and the summed length of
    the counted instructions' contexts.  A slot is in the context while it
    has not retired (``core.simnet.in_flight``).
  * **Input.**  The host packs each row into two int32 words (8 B per
    instruction, ``core.simnet.pack_rows``) once per request and hands each
    launch one slice of that array; the launch cuts its sub-traces out of
    the slice and expands the words into features on the device.
  * **Output.**  Each row's cycles (retire of its last counted instruction
    minus the clock before its first) and context sum: one small pull per
    request.  Every prediction's integer (f, c) stays on the device in the
    result (``SimNetResult.latencies`` pulls it).

The step is chosen by the model's kind (``make_engine``) and cached like
Tao's (``_CachedStep``, keyed on the ``SimNetConfig``), with the same AOT
``warmup``; Tao's steps and keys are untouched.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core.simnet import (
    ROW_FIELDS,
    SimNetConfig,
    context_inputs,
    is_valid,
    pack_rows,
    round_latencies,
    simnet_forward,
)
from ..spans import call_span, span
from .metrics import detailed_input_mpki
from .runner import EngineConfig, SimulationResult, StreamingEngine, _CachedStep

__all__ = ["SimNetEngine", "SimNetResult", "make_engine", "subtrace_sequences"]


def make_engine(params: Dict, cfg, ecfg: EngineConfig = EngineConfig(), **kw) -> StreamingEngine:
    """The engine for a model's kind: SimNet's sequential one for a
    ``SimNetConfig``, Tao's otherwise."""
    cls = SimNetEngine if isinstance(cfg, SimNetConfig) else StreamingEngine
    return cls(params, cfg, ecfg, **kw)


def subtrace_sequences(words, rows: int, cfg: SimNetConfig):
    """A launch's slice of packed words, (rows * subtrace + warmup, 2), ->
    each sub-trace's instructions in order, (rows, warmup + subtrace, 2):
    row k is words[k * subtrace : k * subtrace + warmup + subtrace]."""
    sub, warm = cfg.subtrace, cfg.warmup
    head = words[: rows * sub].reshape(rows, sub, 2)[:, :warm]
    body = words[warm: warm + rows * sub].reshape(rows, sub, 2)
    return jnp.concatenate([head, body], axis=1)


def counted_from(cfg: SimNetConfig) -> int:
    """The scan iteration of each sub-trace's first counted instruction."""
    return cfg.warmup


class SimNetResult(SimulationResult):
    """A SimNet simulation: CPI from the clock, the detailed trace's own
    branch and L1D MPKI, and every prediction's integer latencies kept on
    the device until ``latencies()`` pulls them."""

    def __init__(self, *args, lat: Sequence = (), subtraces: int = 0, **kw):
        super().__init__(*args, **kw)
        self._lat = list(lat)
        self.subtraces = subtraces

    def latencies(self) -> np.ndarray:
        """(subtraces, warmup + subtrace, 2) int32: each sub-trace's (f, c)
        per scan iteration, warm-up included; zeros where no instruction
        ran."""
        return np.concatenate(jax.device_get(self._lat))[: self.subtraces]


class SimNetEngine(StreamingEngine):
    """SimNet's sequential simulation over parallel sub-traces (module
    docstring).  ``simulate`` takes a detailed trace's rows
    (``core.simnet.ROW_FIELDS``, e.g. ``build_adjusted_trace(...).adjusted``)."""

    # it expands its own features from the rows, on the device
    takes_features = False

    def __init__(self, params: Dict, cfg: SimNetConfig, ecfg: EngineConfig = EngineConfig(), **kw):
        super().__init__(params, cfg, ecfg, **kw)
        if cfg.subtrace < cfg.warmup:
            raise ValueError(f"a sub-trace ({cfg.subtrace}) must be at least its warm-up "
                             f"({cfg.warmup}): each is warmed on the sub-trace before it")
        if self.plan.sharded or self.heads > 1:
            raise ValueError("SimNet runs on one device, one model")
        if ecfg.precision != "fp32" or ecfg.collect:
            raise ValueError("SimNet runs at fp32; its latencies stay in the result "
                             "(SimNetResult.latencies), not in collect=True arrays")

    def batch_rows(self, n: int) -> tuple:
        if n < 1:
            raise ValueError("cannot simulate an empty trace")
        bsz = self.ecfg.batch_size
        full, rem = divmod(-(-n // self.cfg.subtrace), bsz)
        if not rem:
            return (bsz,) * full
        q = max(1, bsz // 16)
        return (bsz,) * full + (-(-rem // q) * q,)

    def init_carry(self, n: int) -> Dict:
        # a launch builds its own state: sub-traces share nothing
        return {}

    def _abstract_batch(self, b: int, w_eff: int):
        cfg = self.cfg
        return jax.ShapeDtypeStruct((b * cfg.subtrace + cfg.warmup, 2), jnp.int32)

    def _build_step(self, rows: int, w_eff: int, entry: _CachedStep):
        cfg = self.cfg
        K = cfg.context
        first = counted_from(cfg)

        def simnet_scan(params, carry, words):
            entry.compiles += 1  # runs at trace time only
            seq = jnp.swapaxes(subtrace_sequences(words, rows, cfg), 0, 1)
            i32 = jnp.int32
            state = {
                "words": jnp.zeros((rows, K, 2), i32),
                "F": jnp.zeros((rows, K), i32),
                "C": jnp.zeros((rows, K), i32),
                "R": jnp.full((rows, K), -1, i32),
                "T": jnp.zeros((rows,), i32),
                "last": jnp.zeros((rows,), i32),
                "t0": jnp.zeros((rows,), i32),
                "ctx": jnp.zeros((rows,), i32),
            }

            def body(st, xs):
                w, t = xs
                valid = is_valid(w)
                T = st["T"]
                x, mask = context_inputs(w, st["words"], st["F"], st["C"], st["R"], T, cfg)
                lat = round_latencies(simnet_forward(params, x))
                F = T + lat[:, 0]
                C = F + lat[:, 1]
                R = jnp.maximum(C, st["last"])

                def push(buf, v):
                    return jnp.concatenate([v[:, None], buf[:, :-1]], 1)

                moved = {"words": push(st["words"], w), "F": push(st["F"], F),
                         "C": push(st["C"], C), "R": push(st["R"], R), "T": F, "last": R}
                new = {k: jnp.where(valid.reshape((rows,) + (1,) * (v.ndim - 1)), v, st[k])
                       for k, v in moved.items()}
                new["t0"] = jnp.where(t == first, T, st["t0"])
                counted = valid & (t >= first)
                new["ctx"] = st["ctx"] + jnp.where(counted, mask.sum(1, dtype=i32), 0)
                return new, jnp.where(valid[:, None], lat, 0)

            st, lat = jax.lax.scan(body, state, (seq, jnp.arange(seq.shape[0])))
            return {"lat": jnp.swapaxes(lat, 0, 1), "cycles": st["last"] - st["t0"],
                    "context": st["ctx"]}

        return jax.jit(simnet_scan)

    # tao: hot
    def simulate(self, rows: np.ndarray, features: Optional[object] = None) -> SimNetResult:
        """Simulate one detailed trace's rows: CPI from the clock over the
        counted instructions, ``n`` of them."""
        t0 = time.perf_counter()
        if features is not None:
            raise ValueError("SimNet expands its own features from the rows")
        names = rows.dtype.names or ()
        missing = [f for f in ROW_FIELDS if f not in names]
        if missing:
            raise ValueError(f"SimNet simulates a detailed trace's rows; missing fields {missing}")
        cfg = self.cfg
        n = len(rows)
        plan = self.batch_rows(n)
        subtraces = -(-n // cfg.subtrace)
        iters = cfg.warmup + cfg.subtrace
        with call_span("simnet.simulate", instructions=n, subtraces=subtraces,
                       iterations=len(plan) * iters, positions=sum(plan) * iters,
                       padded_rows=sum(plan) - subtraces,
                       warmup=cfg.warmup * (subtraces - 1)) as sp:
            steps = {}
            for r in dict.fromkeys(plan):
                entry = self._get_step((r, min(cfg.window, n)))
                steps[r] = ((entry.aot, self._committed_params()) if entry.aot is not None
                            else (entry.fn, self._run_params()))
            with span("simnet.pack"):
                flat = np.zeros((cfg.warmup + sum(plan) * cfg.subtrace, 2), np.int32)
                flat[cfg.warmup: cfg.warmup + n] = pack_rows(rows)
                metrics = detailed_input_mpki(rows)
            outs: List[Dict] = []
            k0 = 0
            for r in plan:
                step, params = steps[r]
                lo = k0 * cfg.subtrace
                with span("simnet.scan", rows=r, iterations=iters):
                    outs.append(step(params, {}, flat[lo: lo + r * cfg.subtrace + cfg.warmup]))
                k0 += r
            with span("simnet.sync"):
                host = jax.device_get([(o["cycles"], o["context"]) for o in outs])
                cycles = ctx = 0
                k0 = 0
                for (cyc, cs), r in zip(host, plan):
                    real = min(r, subtraces - k0)
                    cycles += int(cyc[:real].astype(np.int64).sum())
                    ctx += int(cs[:real].astype(np.int64).sum())
                    k0 += r
                sp.set_metadata(context_rows=ctx / n)
                secs = time.perf_counter() - t0
                metrics.update(cpi=cycles / n, total_cycles=cycles, context_rows=ctx / n)
                return SimNetResult(
                    num_instructions=n, seconds=secs, mips=n / 1e6 / secs, metrics=metrics,
                    lat=[o["lat"] for o in outs], subtraces=subtraces)
